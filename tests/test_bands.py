"""Degree bands of the dense edgeMap sweep (``repro.core.bands``).

The banded sweep reads each block only up to its band's width; it must
give the padded sweep's answers on every backend, filter form and monoid
(exactly for min, max and or; to float rounding for sum), build the same
index from ``build_csr`` and ``compress``, and never carry an index over
a change of the block set."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_csr, compact_live_blocks, compress, make_filter
from repro.core.bands import MIN_BAND_WIDTH, band_widths, check_band_index
from repro.core.compressed import exception_dense
from repro.core.csr import graph_spec
from repro.core.edgemap import edgemap_dense, edgemap_dense_batched, edgemap_reduce
from repro.core.graph_filter import pack_bits
from repro.delta import DeltaOverlay, load_compacted, save_compacted
from repro.obs import Registry, use_registry


def _skewed(n, seed, block_size, weighted=True, spread=1):
    """Random edges plus hubs of degree 1..3 blocks, so every band shows."""
    rng = np.random.default_rng(seed)
    src = [rng.integers(0, n, 6 * n)]
    dst = [rng.integers(0, n, 6 * n)]
    for hub, deg in ((0, 3 * block_size), (1, block_size + 1), (2, block_size // 2 + 3)):
        src.append(np.full(deg, hub))
        dst.append(rng.choice(np.arange(3, n, spread), deg, replace=False))
    src, dst = np.concatenate(src), np.concatenate(dst)
    w = rng.random(src.size).astype(np.float32) + 0.5 if weighted else None
    return build_csr(n, src, dst, w, block_size=block_size)


def _exception_dense():
    """No id-locality: each vertex's two neighbours lie >= 2^16 apart, and
    one hub owns a full block and a partial one."""
    n, k = 200_000, 2000
    src = np.concatenate([np.repeat(np.arange(k), 2), np.full(40, k)])
    dst = np.concatenate([
        np.stack([np.arange(k) + 1, np.arange(k) + 150_000], axis=1).reshape(-1),
        np.arange(40) * 4000 + 7,
    ])
    return build_csr(n, src, dst, block_size=32)


@pytest.fixture(scope="module")
def graphs():
    csr = _skewed(400, 0, 64)
    # ids spread over n > 2^16: adjacency gaps >= 2^16 escape as exceptions
    wide = compress(_skewed(70_000, 1, 32, spread=211))
    dense = compress(_exception_dense())
    assert 0 < wide.n_exceptions and not exception_dense(wide)
    assert exception_dense(dense)
    return {"csr": csr, "compressed": wide, "exception_dense": dense}


def _padded(g):
    return dataclasses.replace(g, bands=None)


def _active(g, form, seed=5):
    """A random edge filter over ``g`` in one of its three forms."""
    if form is None:
        return None
    rng = np.random.default_rng(seed)
    mask = np.asarray(g.edge_valid) & (rng.random(g.num_blocks * g.block_size) < 0.6)
    if form == "mask":
        return jnp.asarray(mask)
    words = pack_bits(jnp.asarray(mask).reshape(g.num_blocks, g.block_size))
    if form == "words":
        return words
    return dataclasses.replace(make_filter(g), bits=words)


def _inputs(g, monoid, seed=3):
    rng = np.random.default_rng(seed)
    frontier = jnp.asarray(rng.random(g.n) < 0.5)
    if monoid == "or":
        return frontier, jnp.asarray(rng.random(g.n) < 0.3), lambda xs, w: xs
    x = jnp.asarray(rng.random(g.n).astype(np.float32))
    if monoid == "sum":
        return frontier, x, lambda xs, w: xs * w
    return frontier, x, lambda xs, w: xs + w


def _assert_same(got, want, monoid):
    out, touched = got
    np.testing.assert_array_equal(np.asarray(touched), np.asarray(want[1]))
    if monoid == "sum":
        np.testing.assert_allclose(np.asarray(out), np.asarray(want[0]), rtol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want[0]))


@pytest.mark.parametrize("monoid", ["min", "max", "or", "sum"])
@pytest.mark.parametrize("active", [None, "filter", "words", "mask"])
@pytest.mark.parametrize("graph", ["csr", "compressed", "exception_dense"])
def test_banded_sweep_matches_padded(graphs, graph, active, monoid):
    g = graphs[graph]
    assert g.bands is not None and len(g.bands.widths) > 1
    frontier, x, fn = _inputs(g, monoid)
    ea = _active(g, active)
    kw = dict(monoid=monoid, map_fn=fn, edge_active=ea)
    _assert_same(
        edgemap_dense(g, frontier, x, **kw), edgemap_dense(_padded(g), frontier, x, **kw), monoid
    )


@pytest.mark.parametrize("monoid", ["min", "sum"])
@pytest.mark.parametrize("graph", ["csr", "compressed", "exception_dense"])
def test_batched_banded_sweep_matches_padded_and_single(graphs, graph, monoid):
    """Batched rounds route like single ones: each lane equals the padded
    batched sweep, and is bit-identical to its own single-query sweep."""
    g = graphs[graph]
    rng = np.random.default_rng(11)
    B = 3
    masks = jnp.asarray(rng.random((B, g.n)) < 0.5)
    xb = jnp.asarray(rng.random((B, g.n)).astype(np.float32))
    fn = (lambda xs, w: xs * w) if monoid == "sum" else (lambda xs, w: xs + w)
    lanes = jnp.asarray([True, False, True])
    ea = _active(g, "words")
    kw = dict(monoid=monoid, map_fn=fn, edge_active=ea, map_lanes=lanes)
    out, touched = edgemap_dense_batched(g, masks, xb, **kw)
    _assert_same((out, touched), edgemap_dense_batched(_padded(g), masks, xb, **kw), monoid)
    for i in range(B):
        lane_fn = fn if bool(lanes[i]) else (lambda xs, w: xs)
        o, t = edgemap_dense(g, masks[i], xb[i], monoid=monoid, map_fn=lane_fn, edge_active=ea)
        np.testing.assert_array_equal(np.asarray(out[i]), np.asarray(o))
        np.testing.assert_array_equal(np.asarray(touched[i]), np.asarray(t))


def test_feature_state_banded_matches_padded(graphs):
    g = graphs["csr"]
    rng = np.random.default_rng(2)
    frontier = jnp.asarray(rng.random(g.n) < 0.5)
    x = jnp.asarray(rng.random((g.n, 3)).astype(np.float32))
    kw = dict(monoid="max", map_fn=lambda xs, w: xs * w)
    _assert_same(
        edgemap_dense(g, frontier, x, **kw), edgemap_dense(_padded(g), frontier, x, **kw), "max"
    )


def _degrees_graph():
    """Vertices of degree 0, 1, 128, 129 and 256 (block size 128)."""
    degs = {1: 1, 2: 128, 3: 129, 4: 256}  # vertex 0 has none
    src = np.concatenate([np.full(d, v) for v, d in degs.items()])
    dst = np.concatenate([np.arange(5, 5 + d) for d in degs.values()])
    return build_csr(400, src, dst, block_size=128)


def test_degree_edges_land_in_their_bands():
    g = _degrees_graph()
    # blocks: v1 [1], v2 [128], v3 [128, 1], v4 [128, 128]
    assert g.num_blocks == 6
    assert g.bands.widths == (MIN_BAND_WIDTH, 128) and g.bands.sizes == (2, 4)
    ids = np.asarray(g.bands.block_ids)
    np.testing.assert_array_equal(ids, [0, 3, 1, 2, 4, 5])
    np.testing.assert_array_equal(np.asarray(g.bands.owners), [1, 3, 2, 3, 4, 4])
    frontier, x, fn = _inputs(g, "min")
    for mono in ("min", "sum"):
        kw = dict(monoid=mono, map_fn=fn)
        _assert_same(
            edgemap_dense(g, frontier, x, **kw), edgemap_dense(_padded(g), frontier, x, **kw), mono
        )


@pytest.mark.parametrize(
    "src,dst,widths",
    [
        # every vertex of degree <= 8: one band
        (np.arange(300) % 100, (np.arange(300) * 7 + 1) % 100, (MIN_BAND_WIDTH,)),
        # one edge: one block
        (np.array([0]), np.array([1]), (MIN_BAND_WIDTH,)),
        # no edge: the dummy block, owned by the sentinel
        (np.array([], np.int64), np.array([], np.int64), (MIN_BAND_WIDTH,)),
    ],
    ids=["one_band", "one_block", "no_edge"],
)
def test_one_band_graphs(src, dst, widths):
    g = build_csr(100, src, dst, block_size=32)
    assert g.bands.widths == widths and sum(g.bands.sizes) == g.num_blocks
    frontier, x, fn = _inputs(g, "min")
    c = compress(g)
    for h in (g, c):
        _assert_same(
            edgemap_dense(h, frontier, x, map_fn=fn),
            edgemap_dense(_padded(h), frontier, x, map_fn=fn),
            "min",
        )


@pytest.mark.parametrize("graph", ["csr", "compressed", "exception_dense"])
def test_index_invariants(graphs, graph):
    g = graphs[graph]
    b = g.bands
    ids = np.asarray(b.block_ids)
    np.testing.assert_array_equal(np.sort(ids), np.arange(g.num_blocks))
    assert sum(b.sizes) == g.num_blocks and b.widths == tuple(sorted(set(b.widths)))
    valid = (np.asarray(g.edge_valid).reshape(g.num_blocks, g.block_size)).sum(axis=1)
    np.testing.assert_array_equal(
        np.repeat(b.widths, b.sizes), band_widths(valid[ids], g.block_size)
    )
    # each block's valid slots fit its band's width, and only the narrowest
    # band holds blocks that a narrower width would fit
    w = np.repeat(b.widths, b.sizes)
    assert np.all(valid[ids] <= w)
    assert np.all((valid[ids] > w // 2) | (w == min(MIN_BAND_WIDTH, g.block_size)))
    np.testing.assert_array_equal(np.asarray(b.owners), np.asarray(g.block_src)[ids])
    check_band_index(b, valid, g.block_src, g.block_size)


def test_build_csr_and_compress_agree(graphs):
    g = graphs["csr"]
    c = compress(g)
    assert c.bands is g.bands
    check_band_index(c.bands, c.valid_count, c.block_src, c.block_size)


def test_compress_refuses_a_stale_index(graphs):
    g = graphs["csr"]
    other = _skewed(400, 9, 64)
    with pytest.raises(ValueError):
        compress(dataclasses.replace(g, bands=other.bands))


@pytest.mark.parametrize("graph", ["csr", "compressed"])
def test_shards_take_the_padded_sweep_with_the_same_answers(graphs, graph):
    g = graphs[graph]
    frontier, x, fn = _inputs(g, "min")
    want = edgemap_dense(g, frontier, x, map_fn=fn)
    parts = [edgemap_dense(s, frontier, x, map_fn=fn) for s in g.shard(3)]
    assert all(s.bands is None for s in g.shard(3))
    out = jnp.min(jnp.stack([p[0] for p in parts]), axis=0)
    touched = jnp.any(jnp.stack([p[1] for p in parts]), axis=0)
    _assert_same((out, touched), want, "min")


@pytest.mark.parametrize("graph", ["csr", "compressed"])
def test_compacted_graph_carries_a_fresh_index(graphs, graph):
    g = graphs[graph]
    ea = _active(g, "words", seed=8)
    # a filter that kills some blocks whole
    ea = ea.at[::3].set(0)
    g_live, words, _ = compact_live_blocks(g, ea)
    assert g_live.num_blocks < g.num_blocks
    valid = np.asarray(g_live.edge_valid).reshape(g_live.num_blocks, -1).sum(axis=1)
    check_band_index(g_live.bands, valid, g_live.block_src, g.block_size)
    frontier, x, fn = _inputs(g, "min")
    _assert_same(
        edgemap_dense(g_live, frontier, x, map_fn=fn, edge_active=words),
        edgemap_dense(g, frontier, x, map_fn=fn, edge_active=ea),
        "min",
    )
    unbanded, _, _ = compact_live_blocks(_padded(g), ea)
    assert unbanded.bands is None


def test_delta_graph_takes_the_padded_sweep(graphs):
    g = graphs["csr"]
    ov = DeltaOverlay(g)
    ov.insert(5, 7, 2.0)
    ov.delete(0, int(np.asarray(g.edge_dst)[0]))
    snap = ov.snapshot()
    src, dst, w = ov.live_edges()
    rebuilt = build_csr(g.n, src, dst, w, block_size=g.block_size)
    frontier, x, fn = _inputs(g, "min")
    reg = Registry()
    with use_registry(reg):
        got = edgemap_dense(snap, frontier, x, map_fn=fn)
    gauge = reg.get("sage_dense_sweep_slots")
    assert gauge.value(kind="scattered") == gauge.value(kind="padded")
    _assert_same(got, edgemap_dense(rebuilt, frontier, x, map_fn=fn), "min")


def test_restored_compacted_base_rebuilds_its_index(graphs, tmp_path):
    c = graphs["compressed"]
    save_compacted(str(tmp_path), 1, c)
    restored, _ = load_compacted(str(tmp_path))
    assert (restored.bands.widths, restored.bands.sizes) == (c.bands.widths, c.bands.sizes)
    np.testing.assert_array_equal(
        np.asarray(restored.bands.block_ids), np.asarray(c.bands.block_ids)
    )
    np.testing.assert_array_equal(np.asarray(restored.bands.owners), np.asarray(c.bands.owners))


def test_graph_spec_takes_the_padded_sweep():
    spec = graph_spec(64, 6, 32)
    assert spec.bands is None
    out = jax.eval_shape(
        lambda gg, fr, x: edgemap_reduce(gg, fr, x, monoid="min", mode="dense"),
        spec,
        jax.ShapeDtypeStruct((64,), jnp.bool_),
        jax.ShapeDtypeStruct((64,), jnp.int32),
    )
    assert out[0].shape == (64,)


@pytest.mark.parametrize("batched", [False, True], ids=["single", "batched"])
def test_gauge_reads_banded_on_a_banded_graph_and_padded_on_a_shard(graphs, batched):
    g = graphs["csr"]
    frontier, x, _ = _inputs(g, "min")

    def sweep(h):
        if batched:
            return edgemap_dense_batched(h, frontier[None], x[None])
        return edgemap_dense(h, frontier, x)

    # a band's blocks pack F_B // w to a row, in whole tiles of 8 rows
    fb = g.block_size
    banded = sum(-(-r // (8 * (fb // w))) * 8 * fb for w, _, r in g.bands.spans())
    assert banded < g.num_blocks * fb
    for h, scattered in ((g, banded), (g.shard(2)[0], None)):
        reg = Registry()
        with use_registry(reg):
            jax.jit(sweep).lower(h)  # a record at trace time
        gauge = reg.get("sage_dense_sweep_slots")
        padded = h.num_blocks * h.block_size
        assert gauge.value(kind="padded") == padded
        assert gauge.value(kind="scattered") == (padded if scattered is None else scattered)
