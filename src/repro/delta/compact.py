"""Compaction — fold the DRAM overlay into a fresh ``CompressedCSR``.

The ONE large-memory write in the whole mutable-graph subsystem: the
overlay's live edge set (base minus tombstones plus patch) re-encodes as
a fresh compressed base in a single batched pass, charged at the PSAM's
ω write premium (``PSAMCost.charge_large_write``) so the ``ω·W / edits``
amortization the asymmetric-building-blocks line of work argues for
(arXiv:1806.10370) is visible in the model, not just asserted.

Persistence rides ``repro.checkpoint.ckpt``'s atomic step-directory save
(write to ``step_N.tmp``, ``os.replace`` to publish): a crash at ANY
point during a compaction save leaves the previous published step as the
restore target — recovery loads the pre- or post-compaction graph, never
a torn state (locked by the subprocess kill tests in
``tests/test_delta.py``).  The checkpoint tree is a plain dict of named
leaves with the static meta serialized as a JSON byte leaf, so
``restore`` can rebuild its treedef without an example graph.
"""
from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np

from ..checkpoint import ckpt
from ..core.bands import band_index
from ..core.compressed import CompressedCSR, compress
from ..core.csr import build_csr
from ..obs import get_registry
from .overlay import DeltaOverlay

__all__ = [
    "compact",
    "compact_write_words",
    "load_compacted",
    "save_compacted",
]

# static key set: every save carries every field (zero-size arrays when a
# field is empty/absent) so the checkpoint treedef never varies and
# ``restore`` can always rebuild it from the key list alone
_ARRAY_KEYS = (
    "block_first",
    "deltas",
    "valid_count",
    "exc_block",
    "exc_slot",
    "exc_value",
    "block_src",
    "degrees",
    "block_weights",
)


def compact_write_words(c: CompressedCSR) -> int:
    """NVRAM words one compaction writes: the compressed footprint
    (first + valid count + deltas + COO exceptions, bytes rounded up to
    words) plus the uncompressed weight blocks when weighted — the exact
    mirror of what ``_block_read_words`` charges to *read* this graph."""
    words = -(-c.compressed_bytes // 4)
    if c.weighted:
        words += c.block_size * c.num_blocks
    return words


def compact(
    overlay: DeltaOverlay,
    *,
    cost=None,
    ckpt_dir: str | None = None,
    step: int = 0,
    keep: int = 3,
    registry=None,
) -> CompressedCSR:
    """Fold ``overlay`` into a fresh ``CompressedCSR`` base.

    Gathers the live edge set host-side, rebuilds through the same
    ``build_csr`` → ``compress`` pipeline a cold load uses (so the
    result is bit-identical to a from-scratch graph over the same
    edges), and — when ``cost`` is a ``PSAMCost`` — charges the
    compacted footprint as the subsystem's ONLY ``charge_large_write``.
    ``ckpt_dir`` persists the result atomically via
    :func:`save_compacted`.  The overlay itself is left untouched;
    callers rebase by constructing ``DeltaOverlay(new_base)``.
    """
    src, dst, w = overlay.live_edges()
    rebuilt = build_csr(
        overlay.n,
        src,
        dst,
        w if overlay.weighted else None,
        block_size=overlay.block_size,
        symmetrize=False,
    )
    c = compress(rebuilt)
    words = compact_write_words(c)
    if cost is not None:
        cost.charge_large_write(words, label="compact")
    reg = registry if registry is not None else get_registry()
    if reg.enabled:
        reg.counter(
            "sage_delta_compactions_total", "overlay compactions executed"
        ).inc()
        reg.gauge(
            "sage_delta_last_compact_write_words",
            "NVRAM words written by the most recent compaction",
        ).set(float(words))
    if ckpt_dir is not None:
        save_compacted(ckpt_dir, step, c, keep=keep)
    return c


def _ckpt_tree(c: CompressedCSR) -> dict:
    meta = {
        "n": c.n,
        "m": c.m,
        "num_blocks": c.num_blocks,
        "block_size": c.block_size,
        "n_exceptions": c.n_exceptions,
        "weighted": c.weighted,
    }
    tree = {}
    for k in _ARRAY_KEYS:
        v = getattr(c, k)
        tree[k] = (
            np.zeros((0, 0), np.float32) if v is None else np.asarray(v)
        )
    tree["meta"] = np.frombuffer(json.dumps(meta).encode(), np.uint8).copy()
    return tree


def save_compacted(ckpt_dir: str, step: int, c: CompressedCSR, *, keep: int = 3) -> str:
    """Persist one compacted base atomically (ckpt step-directory save).

    All-or-nothing by construction: arrays + manifest land in
    ``step_N.tmp`` and one ``os.replace`` publishes the directory, so a
    reader never observes a half-written step."""
    return ckpt.save(ckpt_dir, step, _ckpt_tree(c), keep=keep)


def load_compacted(
    ckpt_dir: str, step: int | None = None
) -> tuple[CompressedCSR | None, int | None]:
    """Load a persisted compacted base; ``(None, None)`` when none exists.

    ``step=None`` loads the latest *published* step — unpublished
    ``.tmp`` directories from a crashed save are invisible, which is the
    crash-safety contract: recovery sees the pre-compaction graph until
    the moment the post-compaction save's ``os.replace`` lands.  The dense
    sweep's band index is not saved: it is rebuilt from the valid counts.
    """
    if step is None:
        step = ckpt.latest_step(ckpt_dir)
        if step is None:
            return None, None
    example = {k: 0 for k in (*_ARRAY_KEYS, "meta")}
    tree = ckpt.restore(ckpt_dir, step, example)
    meta = json.loads(bytes(tree["meta"]))
    weighted = bool(meta["weighted"])
    block_src = jnp.asarray(tree["block_src"], jnp.int32)
    c = CompressedCSR(
        block_first=jnp.asarray(tree["block_first"], jnp.int32),
        deltas=jnp.asarray(tree["deltas"], jnp.uint16),
        valid_count=jnp.asarray(tree["valid_count"], jnp.uint16),
        exc_block=jnp.asarray(tree["exc_block"], jnp.int32),
        exc_slot=jnp.asarray(tree["exc_slot"], jnp.int32),
        exc_value=jnp.asarray(tree["exc_value"], jnp.int32),
        block_src=block_src,
        degrees=jnp.asarray(tree["degrees"], jnp.int32),
        n=int(meta["n"]),
        m=int(meta["m"]),
        num_blocks=int(meta["num_blocks"]),
        block_size=int(meta["block_size"]),
        n_exceptions=int(meta["n_exceptions"]),
        block_weights=(
            jnp.asarray(tree["block_weights"], jnp.float32) if weighted else None
        ),
        weighted=weighted,
        bands=band_index(tree["valid_count"], block_src, int(meta["block_size"])),
    )
    return c, step
