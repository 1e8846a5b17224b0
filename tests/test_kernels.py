"""Per-kernel allclose sweeps (shapes x dtypes) against the ref.py oracles,
all in interpret mode (the kernel body executes in Python on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.embedding_bag.ops import embedding_bag
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.frontier_compact.ops import frontier_compact
from repro.kernels.frontier_compact.ref import frontier_compact_ref
from repro.kernels.grouped_matmul.ops import grouped_matmul
from repro.kernels.grouped_matmul.ref import grouped_matmul_ref
from repro.kernels.hyb_gather.ops import hyb_gather
from repro.kernels.hyb_gather.ref import hyb_gather_ref
from repro.kernels.segment_spmm.ops import segment_spmm
from repro.kernels.segment_spmm.ref import segment_spmm_ref

RNG = np.random.default_rng(0)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("m,d,n", [(100, 8, 40), (513, 1, 129), (2048, 64, 511), (1000, 200, 77)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_spmm_sweep(m, d, n, dtype):
    msg = jnp.asarray(RNG.standard_normal((m, d)), dtype)
    seg = jnp.asarray(RNG.integers(0, n, m), jnp.int32)
    valid = jnp.asarray(RNG.random(m) < 0.8)
    got = segment_spmm(msg, seg, n, valid)
    want = segment_spmm_ref(msg.astype(jnp.float32), seg, n, valid).astype(dtype)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("m,c,density", [(100, 1, 0.5), (1024, 4, 0.1), (700, 2, 0.9), (512, 3, 0.0)])
def test_frontier_compact_sweep(m, c, density):
    vals = jnp.asarray(RNG.standard_normal((m, c)), jnp.float32)
    mask = jnp.asarray(RNG.random(m) < density)
    got, cnt = frontier_compact(vals, mask)
    want, wcnt = frontier_compact_ref(vals, mask)
    assert int(cnt) == int(wcnt)
    k = int(cnt)
    np.testing.assert_allclose(got[:k], want[:k])


@pytest.mark.parametrize("m,c,a", [(300, 1, 8), (1000, 3, 33), (64, 2, 4)])
def test_hyb_gather_sweep(m, c, a):
    edges = jnp.asarray(RNG.standard_normal((m, c)), jnp.float32)
    starts = jnp.asarray(RNG.integers(0, m, a), jnp.int32)
    degs = jnp.asarray(RNG.integers(0, 120, a), jnp.int32)
    got = hyb_gather(edges, starts, degs)
    want = hyb_gather_ref(edges, starts, degs)
    np.testing.assert_allclose(got, want)


@pytest.mark.parametrize("S,L,dh,window", [(128, 128, 64, 0), (300, 300, 64, 64), (257, 257, 128, 0), (64, 512, 32, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, L, dh, window, dtype):
    if S > L:
        pytest.skip("decode-style only")
    q = jnp.asarray(RNG.standard_normal((2, S, dh)), dtype)
    # causal masking over the shared position space needs S == L here
    k = jnp.asarray(RNG.standard_normal((2, L, dh)), dtype)[:, :S]
    v = jnp.asarray(RNG.standard_normal((2, L, dh)), dtype)[:, :S]
    got = flash_attention(q, k, v, window=window)
    want = flash_attention_ref(q, k, v, 1.0 / dh**0.5, window=window)
    np.testing.assert_allclose(
        got.astype(jnp.float32), want.astype(jnp.float32), **_tol(dtype)
    )


@pytest.mark.parametrize("V,D,B,L", [(100, 16, 8, 1), (500, 48, 40, 4), (64, 128, 16, 8)])
@pytest.mark.parametrize("mode", ["sum", "mean", "max"])
def test_embedding_bag_sweep(V, D, B, L, mode):
    t = jnp.asarray(RNG.standard_normal((V, D)), jnp.float32)
    idx = jnp.asarray(RNG.integers(0, V, (B, L)), jnp.int32)
    got = embedding_bag(t, idx, mode=mode)
    want = embedding_bag_ref(t, idx, mode=mode)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("E,D,F", [(4, 32, 48), (8, 64, 128), (3, 16, 16)])
def test_grouped_matmul_sweep(E, D, F):
    counts = jnp.asarray(RNG.integers(0, 200, E), jnp.int32)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1]])
    T = int(jnp.sum(counts)) + 13
    x = jnp.asarray(RNG.standard_normal((T, D)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((E, D, F)), jnp.float32)
    got = grouped_matmul(x, w, starts, counts)
    want = grouped_matmul_ref(x, w, starts, counts)
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_segment_spmm_empty_and_full_valid():
    msg = jnp.ones((64, 4), jnp.float32)
    seg = jnp.zeros(64, jnp.int32)
    none = segment_spmm(msg, seg, 4, jnp.zeros(64, bool))
    assert float(jnp.abs(none).sum()) == 0.0
    full = segment_spmm(msg, seg, 4, jnp.ones(64, bool))
    assert float(full[0, 0]) == 64.0


# ---------------------------------------------------------------- min mode

@pytest.mark.parametrize("m,d,n", [(100, 8, 40), (513, 1, 129), (1000, 16, 77)])
def test_segment_spmm_min_sweep(m, d, n):
    """combine='min' must be BIT-exact vs segment_min (the FILTER-engine
    contract: min of a fixed multiset is order-independent)."""
    msg = jnp.asarray(RNG.standard_normal((m, d)), jnp.float32)
    seg = jnp.asarray(RNG.integers(0, n, m), jnp.int32)
    valid = jnp.asarray(RNG.random(m) < 0.8)
    got = segment_spmm(msg, seg, n, valid, combine="min")
    want = segment_spmm_ref(msg, seg, n, valid, combine="min")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segment_spmm_min_inf_messages():
    """±inf messages (the MIN identity rides real frontiers) must survive
    the masked-select path — the 0*inf=NaN trap that rules out the matmul."""
    msg = jnp.asarray([jnp.inf, 1.0, -jnp.inf, jnp.inf], jnp.float32)[:, None]
    seg = jnp.asarray([0, 0, 1, 2], jnp.int32)
    got = segment_spmm(msg, seg, 4, combine="min")[:, 0]
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray([1.0, -np.inf, np.inf, np.inf], np.float32))


# ------------------------------------------------ prebuilt route (routed fold)

def _routed_graph():
    """A skewed graph whose destinations skip output blocks 1 and 3 and
    whose partitions are all shorter than the block but one."""
    from repro.graph.csr import csr_from_edges

    rng = np.random.default_rng(7)
    n, m = 5 * 128 + 17, 3000
    src = (rng.pareto(1.2, m) * 40).astype(np.int64) % n
    dst = rng.integers(0, n, m)
    dst = np.where((dst // 128) % 2 == 1, dst - 128, dst)  # blocks 1, 3 empty
    return csr_from_edges(n, src, dst, rng.random(m).astype(np.float32))


@pytest.mark.parametrize("d", [1, 2])
@pytest.mark.parametrize("combine", ["min", "sum"])
def test_segment_spmm_routed_matches_per_call(combine, d):
    """The fold over a block routed when the runtime is built equals the
    per-call route over the same partition's CSR slice: bit-identical for
    min, within the sum sweep's tolerance for sum.  Pad lanes hold
    garbage messages the fold must ignore; blocks 1 and 3 receive no
    edge; every partition but the largest is shorter than B."""
    from repro.core.partition import partition_graph, route_partitions, to_device_partitions
    from repro.kernels.segment_spmm.ops import segment_spmm_routed
    from repro.kernels.segment_spmm.segment_spmm import LANES

    g = _routed_graph()
    n = g.n_nodes
    table = partition_graph(g, n_partitions=6)
    B = to_device_partitions(table, n, g.n_edges).block_size
    route = route_partitions(g, table, B)
    assert len(set(table.edges_per_partition)) > 1 and route.width > B
    src_all = g.edge_sources().astype(np.float32)
    for p in range(table.n_partitions):
        e0, e1 = table.edge_start[p], table.edge_start[p + 1]
        count = e1 - e0
        cols = [np.asarray(route.weight[p]), np.asarray(route.src[p], np.float32)][:d]
        routed = np.stack(cols).reshape(d, -1, LANES)
        routed[:, np.arange(route.width).reshape(-1, LANES) >= count] = -7.0
        per_call = np.stack([g.weights[e0:e1], src_all[e0:e1]][:d], axis=-1)
        got = segment_spmm_routed(
            jnp.asarray(routed), route.dst[p].reshape(-1, LANES), route.first[p],
            route.last[p], n, combine=combine)
        want = segment_spmm(jnp.asarray(per_call), jnp.asarray(g.indices[e0:e1]), n,
                            combine=combine)
        if combine == "min":
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        else:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), **_tol(jnp.float32))
        empty = np.asarray(got)[128:256]
        assert np.all(empty == (np.inf if combine == "min" else 0.0))


# -------------------------------------------- degenerate shapes (regressions)

def test_segment_spmm_empty_edge_stream():
    """m==0 previously exploded in BlockSpec slicing; it must return the
    combiner identity for every segment."""
    out = segment_spmm(jnp.zeros((0, 3), jnp.float32), jnp.zeros((0,), jnp.int32), 5)
    assert out.shape == (5, 3) and float(jnp.abs(out).sum()) == 0.0
    out = segment_spmm(jnp.zeros((0,), jnp.float32), jnp.zeros((0,), jnp.int32), 4,
                       combine="min")
    assert out.shape == (4,) and bool(jnp.all(jnp.isinf(out)))


def test_frontier_compact_empty_input():
    """m==0 regression: a zero-step grid would leave count uninitialized."""
    for shape in ((0,), (0, 2)):
        vals, cnt = frontier_compact(jnp.zeros(shape, jnp.float32),
                                     jnp.zeros((0,), bool))
        assert vals.shape == shape and int(cnt) == 0


def test_frontier_compact_nothing_survives():
    vals, cnt = frontier_compact(jnp.arange(8, dtype=jnp.float32),
                                 jnp.zeros(8, bool))
    assert int(cnt) == 0 and vals.shape == (8,)


def test_hyb_gather_no_requests():
    """a==0 regression (an iteration with an empty ZC window list)."""
    out = hyb_gather(jnp.ones((10, 4), jnp.float32),
                     jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.int32))
    assert out.shape[0] == 0 and out.ndim == 3


def test_segment_spmm_unobserved_segments():
    """n_segments far beyond any observed dst: tail segments must hold the
    identity, not garbage from the padded one-hot tiles."""
    msg = jnp.ones((4, 2), jnp.float32)
    seg = jnp.asarray([0, 0, 1, 1], jnp.int32)
    out = np.asarray(segment_spmm(msg, seg, 300))
    assert out.shape == (300, 2)
    np.testing.assert_array_equal(out[:2], np.full((2, 2), 2.0, np.float32))
    assert not out[2:].any()
    mn = np.asarray(segment_spmm(msg, seg, 300, combine="min"))
    np.testing.assert_array_equal(mn[:2], np.ones((2, 2), np.float32))
    assert np.isinf(mn[2:]).all()


def test_segment_spmm_1d_squeeze():
    """1-D messages route through the (m, 1) kernel and squeeze back."""
    msg = jnp.asarray(RNG.standard_normal(200), jnp.float32)
    seg = jnp.asarray(RNG.integers(0, 30, 200), jnp.int32)
    for combine in ("sum", "min"):
        got = segment_spmm(msg, seg, 30, combine=combine)
        assert got.shape == (30,)
        want = segment_spmm_ref(msg[:, None], seg, 30, combine=combine)[:, 0]
        tol = {} if combine == "min" else dict(atol=2e-5, rtol=2e-5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)


# ------------------------------------------- tracing contexts (vmap / loop)

def test_segment_spmm_under_vmap():
    """The engine kernels run inside vmapped service lanes: batched
    min-SpMM must stay bit-exact vs the batched oracle."""
    B, m, n = 3, 257, 40
    msgs = jnp.asarray(RNG.standard_normal((B, m)), jnp.float32)
    seg = jnp.asarray(RNG.integers(0, n, m), jnp.int32)
    got = jax.vmap(lambda mm: segment_spmm(mm, seg, n, combine="min"))(msgs)
    want = jax.vmap(
        lambda mm: segment_spmm_ref(mm[:, None], seg, n, combine="min")[:, 0]
    )(msgs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_frontier_compact_under_vmap():
    B, m = 3, 300
    vals = jnp.asarray(RNG.standard_normal((B, m)), jnp.float32)
    masks = jnp.asarray(RNG.random((B, m)) < 0.4)
    got, cnt = jax.vmap(frontier_compact)(vals, masks)
    want, wcnt = jax.vmap(frontier_compact_ref)(vals, masks)
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(wcnt))
    for i in range(B):
        k = int(cnt[i])
        np.testing.assert_array_equal(np.asarray(got[i, :k]),
                                      np.asarray(want[i, :k]))


def test_segment_spmm_inside_while_loop():
    """The chunked driver calls the kernels from a lax.while_loop body;
    the loop-carried relaxation must match the oracle's loop bit-exactly."""
    m, n = 300, 64
    src = jnp.asarray(RNG.integers(0, n, m), jnp.int32)
    dst = jnp.asarray(RNG.integers(0, n, m), jnp.int32)
    w = jnp.asarray(RNG.random(m), jnp.float32) + 0.5

    def step(kernel):
        def body(state):
            i, x = state
            msg = x[src] + w
            agg = (segment_spmm(msg, dst, n, combine="min") if kernel
                   else segment_spmm_ref(msg[:, None], dst, n, combine="min")[:, 0])
            return i + 1, jnp.minimum(x, agg)

        x0 = jnp.full((n,), jnp.inf, jnp.float32).at[0].set(0.0)
        return jax.lax.while_loop(lambda s: s[0] < 5, body, (jnp.int32(0), x0))[1]

    np.testing.assert_array_equal(np.asarray(step(True)), np.asarray(step(False)))
