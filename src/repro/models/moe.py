"""Mixture-of-Experts FFN with HyTM-style dispatch engines.

Beyond-paper mapping of HyTGraph's insight (DESIGN.md §4): token->expert
routing is an active-subset transfer problem — experts are partitions,
routed tokens the active set.  Three dispatch engines mirror the paper's
three transfer engines:

* ``dense``  (≙ ExpTM-filter): every expert processes every token, the
  top-k combine mask discards the redundant work.  No dispatch machinery
  at all; wins only when E is tiny or nearly all (token, expert) pairs
  are live — exactly the paper's high-activeness regime.
* ``sorted`` (≙ ExpTM-compaction): tokens argsorted by expert id into
  dense contiguous groups, processed as capacity-padded chunks (grouped
  GEMM), then unsorted.  Extra compaction pass (the sort), minimal
  redundant compute.
* ``gather`` (≙ ImpTM-zero-copy): tokens scattered straight into per-
  expert capacity buffers via cumulative-rank slots — fine-grained
  random access, no sort pass.

Distributed (EP) execution shard_maps over the ``data`` axis: the
dispatch buffer is exchanged with ``all_to_all`` (compacted frontier
exchange — the two-level HyTM of DESIGN.md §2), expert FFNs are
tensor-parallel over ``model`` with one psum.

Engine selection: ``dispatch='auto'`` resolves at trace time from config
shape statistics (E, top_k, expected load) via ``select_dispatch_engine``;
runtime per-batch selection is available in the eager path.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import dense_init, swiglu


@dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff: int                  # per-expert hidden dim
    n_shared: int = 0
    d_ff_shared: int = 0       # defaults to n_shared * d_ff
    capacity_factor: float = 1.25
    dispatch: str = "auto"     # 'dense' | 'sorted' | 'gather' | 'auto'
    chunk_tokens: int = 0      # >0: process tokens in chunks (memory bound)
    aux_loss_weight: float = 0.001

    @property
    def shared_hidden(self) -> int:
        return self.d_ff_shared or self.n_shared * self.d_ff

    def replace(self, **kw) -> "MoEConfig":
        import dataclasses
        return dataclasses.replace(self, **kw)


def select_dispatch_engine(cfg: MoEConfig, n_tokens: int) -> str:
    """Trace-time engine choice (HyTM cost model, §4 of DESIGN.md).

    dense cost   ~ E * T * D * F            (all pairs)
    sorted cost  ~ T*K * D * F + sort(T*K)  (compaction pass)
    gather cost  ~ T*K * D * F + T*E slots  (fine-grained scatter)
    dense wins iff E is within ~2x of top_k (nearly-all-active regime);
    gather beats sorted when the slot matrix T*E is cheaper than the sort
    — i.e. for small E.  Mirrors Algorithm 1's tier structure.
    """
    if cfg.dispatch != "auto":
        return cfg.dispatch
    if cfg.n_experts <= 2 * cfg.top_k:
        return "dense"
    if cfg.n_experts <= 32:
        return "gather"
    return "sorted"


def init_moe(key: jax.Array, d_model: int, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 7)
    E, F = cfg.n_experts, cfg.d_ff
    p = {
        "router": dense_init(ks[0], d_model, E, jnp.float32),
        "w_gate": jax.random.normal(ks[1], (E, d_model, F), jnp.float32).astype(dtype) / (d_model ** 0.5),
        "w_up": jax.random.normal(ks[2], (E, d_model, F), jnp.float32).astype(dtype) / (d_model ** 0.5),
        "w_down": jax.random.normal(ks[3], (E, F, d_model), jnp.float32).astype(dtype) / (F ** 0.5),
    }
    if cfg.n_shared > 0:
        Fs = cfg.shared_hidden
        p["shared_gate"] = dense_init(ks[4], d_model, Fs, dtype)
        p["shared_up"] = dense_init(ks[5], d_model, Fs, dtype)
        p["shared_down"] = dense_init(ks[6], Fs, d_model, dtype)
    return p


def _route(x: jax.Array, router: jax.Array, cfg: MoEConfig):
    """fp32 router -> normalized top-k weights + aux load-balance loss."""
    logits = x.astype(jnp.float32) @ router
    probs = jax.nn.softmax(logits, axis=-1)
    topk_w, topk_ids = jax.lax.top_k(probs, cfg.top_k)
    topk_w = topk_w / jnp.maximum(jnp.sum(topk_w, axis=-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux: E * mean_e(frac_tokens_e * mean_prob_e)
    E = cfg.n_experts
    counts = jnp.zeros(E).at[topk_ids.reshape(-1)].add(1.0)
    frac_tokens = counts / jnp.maximum(jnp.sum(counts), 1.0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(frac_tokens * mean_prob)
    return topk_ids.astype(jnp.int32), topk_w.astype(x.dtype), aux


def _expert_ffn(params: dict, xb: jax.Array) -> jax.Array:
    """xb: (E_local, C, D) -> (E_local, C, D_partial) (TP-partial if sharded)."""
    dt = xb.dtype
    h = swiglu(
        jnp.einsum("ecd,edf->ecf", xb, params["w_gate"].astype(dt)),
        jnp.einsum("ecd,edf->ecf", xb, params["w_up"].astype(dt)),
    )
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"].astype(dt))


def _capacity(n_assign: int, n_experts: int, cf: float) -> int:
    c = max(int(n_assign / max(n_experts, 1) * cf), 8)
    return -(-c // 8) * 8


# --------------------------------------------------------------- engines

def _slots_gather(flat_e: jax.Array, E: int, C: int):
    """Zero-copy analogue: per-expert slot via cumulative one-hot rank —
    fine-grained, no sort pass."""
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    ranks = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.take_along_axis(ranks, flat_e[:, None], axis=1)[:, 0]
    keep = slot < C
    return slot, keep


def _slots_sorted(flat_e: jax.Array, E: int, C: int):
    """Compaction analogue: argsort by expert id (the compaction pass),
    slot = rank within the sorted run."""
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    # position within expert group = index - start_of_group
    counts = jnp.zeros(E, dtype=jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(flat_e.shape[0], dtype=jnp.int32) - starts[sorted_e]
    slot = jnp.zeros_like(flat_e).at[order].set(pos)
    keep = slot < C
    return slot, keep


def _moe_core(
    x: jax.Array,            # (T_local, D)
    params: dict,            # local shards when inside shard_map
    cfg: MoEConfig,
    engine: str,
    data_axis: str | None = None,
    model_axis: str | None = None,
):
    """One MoE FFN application. Works standalone (axes None) or inside a
    shard_map region (EP over data_axis, TP over model_axis).

    ``chunk_tokens`` bounds the dispatch-buffer memory: local tokens are
    padded to a chunk multiple and processed under ``lax.map`` — each
    chunk's all_to_all is small, and XLA overlaps chunk k's collective
    with chunk k+1's dispatch compute (multi-stream philosophy)."""
    if cfg.chunk_tokens and x.shape[0] > cfg.chunk_tokens:
        T0, D = x.shape
        c = cfg.chunk_tokens
        n_chunks = -(-T0 // c)
        pad = n_chunks * c - T0
        xp = jnp.pad(x, ((0, pad), (0, 0))).reshape(n_chunks, c, D)
        inner_cfg = cfg.replace(chunk_tokens=0)
        ys, auxs = jax.lax.map(
            lambda xc: _moe_core(xc, params, inner_cfg, engine, data_axis, model_axis),
            xp,
        )
        return ys.reshape(n_chunks * c, D)[:T0], jnp.mean(auxs)
    T, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    topk_ids, topk_w, aux = _route(x, params["router"], cfg)

    if engine == "dense":
        assert data_axis is None, "dense engine is single-shard (filter analogue)"
        # every expert processes every token (redundant), mask-combine.
        def per_expert(carry, e):
            w_g = params["w_gate"][e]
            w_u = params["w_up"][e]
            w_d = params["w_down"][e]
            h = swiglu(x @ w_g.astype(x.dtype), x @ w_u.astype(x.dtype))
            y_e = h @ w_d.astype(x.dtype)
            gate = jnp.sum(
                jnp.where(topk_ids == e, topk_w, 0.0), axis=-1, keepdims=True
            )
            return carry + y_e * gate, None

        y, _ = jax.lax.scan(per_expert, jnp.zeros_like(x), jnp.arange(E))
    else:
        flat_e = topk_ids.reshape(-1)                       # (T*K,)
        tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), K)  # (T*K,)
        C = _capacity(T * K, E, cfg.capacity_factor)
        slot, keep = (_slots_sorted if engine == "sorted" else _slots_gather)(flat_e, E, C)

        buf = jnp.zeros((E, C, D), dtype=x.dtype)
        buf = buf.at[flat_e, jnp.where(keep, slot, C - 1)].add(
            jnp.where(keep[:, None], x[tok], 0.0)
        )

        if data_axis is not None:
            # (E, C, D) -> each device keeps its E/n experts, gathering the
            # slices every peer built for them (compacted frontier exchange).
            buf = jax.lax.all_to_all(buf, data_axis, split_axis=0, concat_axis=1, tiled=True)

        y_buf = _expert_ffn(params, buf)

        if cfg.n_shared > 0:
            shared = swiglu(
                x @ params["shared_gate"].astype(x.dtype),
                x @ params["shared_up"].astype(x.dtype),
            ) @ params["shared_down"].astype(x.dtype)
        else:
            shared = None

        if model_axis is not None:
            # single fused reduction for routed (+ shared) TP partials
            if shared is not None:
                y_buf, shared = jax.lax.psum((y_buf, shared), model_axis)
            else:
                y_buf = jax.lax.psum(y_buf, model_axis)

        if data_axis is not None:
            y_buf = jax.lax.all_to_all(y_buf, data_axis, split_axis=1, concat_axis=0, tiled=True)

        gathered = y_buf[flat_e, jnp.where(keep, slot, C - 1)]
        contrib = jnp.where(keep[:, None], gathered, 0.0) * topk_w.reshape(-1)[:, None]
        y = jnp.zeros_like(x).at[tok].add(contrib)
        if shared is not None:
            y = y + shared
        return y, aux

    # dense path: shared experts + no collectives
    if cfg.n_shared > 0:
        y = y + swiglu(
            x @ params["shared_gate"].astype(x.dtype),
            x @ params["shared_up"].astype(x.dtype),
        ) @ params["shared_down"].astype(x.dtype)
    return y, aux


def moe_ffn(
    params: dict,
    x: jax.Array,             # (T, D) flattened tokens
    cfg: MoEConfig,
    mesh: jax.sharding.Mesh | None = None,
    batch_axes: tuple[str, ...] = ("pod", "data"),
    expert_axis: str | tuple | None = None,
    tp_axis: str = "model",
) -> tuple[jax.Array, jax.Array]:
    """Apply the MoE FFN, optionally distributed via shard_map (EP + TP).

    Experts shard over ALL batch axes by default (('pod','data') on the
    multi-pod mesh): a trillion-param expert bank must not be replicated
    per pod — EP width == DP width keeps the a2a local-per-token while
    fully sharding expert weights (DESIGN.md §5)."""
    if expert_axis is None:
        expert_axis = batch_axes if len(batch_axes) > 1 else batch_axes[0]
    engine = select_dispatch_engine(cfg, x.shape[0])

    def run(xc):
        if mesh is None:
            return _moe_core(xc, params, cfg, engine)
        all_axes = tuple(mesh.axis_names)

        def core(xl, pl):
            y, aux = _moe_core(xl, pl, cfg, engine,
                               data_axis=expert_axis, model_axis=tp_axis)
            return y, jnp.reshape(aux, (1,))

        pspec = {
            "router": P(),
            "w_gate": P(expert_axis, None, tp_axis),
            "w_up": P(expert_axis, None, tp_axis),
            "w_down": P(expert_axis, tp_axis, None),
        }
        if cfg.n_shared > 0:
            pspec.update({
                "shared_gate": P(None, tp_axis),
                "shared_up": P(None, tp_axis),
                "shared_down": P(tp_axis, None),
            })
        fn = jax.shard_map(
            core,
            mesh=mesh,
            in_specs=(P(batch_axes, None), pspec),
            out_specs=(P(batch_axes, None), P(all_axes)),
            check_vma=False,
        )
        y, aux = fn(xc, params)
        return y, jnp.mean(aux)

    return run(x)
