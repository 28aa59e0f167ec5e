import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(REPO, "src"), REPO, os.path.dirname(os.path.abspath(__file__))):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchkit import make_root  # noqa: E402


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
