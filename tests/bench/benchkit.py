"""Helpers of the benchmark's tests: a tiny checkout, and one run in it."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TINY_SCALE = 10


def make_root(tmp_path, scale=TINY_SCALE):
    """A checkout of the benchmark in ``tmp_path``: ``BENCHMARK.json`` and
    ``bench/`` copied, the program linked, every configuration at ``scale``."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root / "BENCHMARK.json")
    os.symlink(os.path.join(REPO, "src"), root / "src")
    if scale is not None:
        spec = json.loads((root / "BENCHMARK.json").read_text())
        for c in spec["configs"]:
            p = root / c["file"]
            cfg = json.loads(p.read_text())
            cfg["generator"]["scale"] = scale
            p.write_text(json.dumps(cfg))
    return root


def run_cell(root, workload, capsys, *, seed=7, seconds=0.2, trace=0):
    """One in-process run on the CPU: (exit code, result line, stderr)."""
    from bench import harness

    argv = ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
            "--trace", str(trace)]
    rc = harness.main(argv, root=str(root), allow_cpu=True)
    captured = capsys.readouterr()
    last = captured.out.strip().splitlines()[-1] if captured.out.strip() else ""
    return rc, (json.loads(last) if last.startswith("{") else None), captured.err
