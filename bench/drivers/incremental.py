"""Incremental SSSP over a stream of edge-update batches
(``keys/update_batches``): the input graph held as a ``DeltaCSR`` with
the configuration's ``delta_csr`` layout, and for each key its batch
applied (``DeltaCSR.apply``) and the source's distances brought up to
date from the last batch's (``run_incremental``), whose values and Δ
come back to the host for the next batch's seeding.

A stream starts from the input graph and the source's from-scratch SSSP
through ``run_hytm`` on the container's view, when its first batch (the
warm-up key) arrives; ``prepare`` builds the container and compiles the
patch scatter for each bucket a batch of the traffic's shape can touch,
as a service that takes updates has before the first one."""

import time

import numpy as np


def buckets(deletes: int, inserts: int) -> list[int]:
    """The power-of-two buckets of the lanes a batch of ``deletes`` and
    ``inserts`` directed ops can touch: at most two a delete (its slot
    and the block's last live lane) and one an insert; the least is
    taken as a quarter of the ops, with room to spare."""
    lo, hi = max((deletes + inserts) // 4, 1), 2 * deletes + inserts
    return [1 << k for k in range((lo - 1).bit_length(), (hi - 1).bit_length() + 1)]


def prepare(config, traffic, edges, jax):
    from repro.core.hytm import HyTMConfig, run_hytm
    from repro.graph.algorithms import ALGORITHMS
    from repro.graph.csr import csr_from_edges
    from repro.stream import OP_DELETE, OP_INSERT, DeltaCSR, EdgeBatch, run_incremental

    t = time.monotonic()
    g = csr_from_edges(edges.n, *edges.directed())
    cfg = HyTMConfig(**config["hytm"])
    program = ALGORITHMS[traffic["program"]]
    shape = traffic["batch"]
    lanes = buckets(2 * shape["deletes"], 2 * shape["inserts"])

    def fresh():
        dcsr = DeltaCSR(g, cfg, **config["delta_csr"])
        for n in lanes:
            dcsr.precompile_patch(n)
        jax.block_until_ready(dcsr.csr)
        return dcsr

    state = {"dcsr": fresh(), "stream": None}

    def begin(stream):
        if state["dcsr"].version:  # another stream starts from the input
            state["dcsr"] = fresh()
        rt = state["dcsr"].runtime_for(program)
        res = run_hytm(None, program, source=stream.source, config=cfg, runtime=rt)
        state.update(stream=stream, values=res.values, delta=res.delta, next=0)

    def run_one(key):
        if key.stream is not state["stream"]:
            begin(key.stream)
        if key.index != state["next"]:
            raise ValueError(f"batch {key.index} arrived where the stream is "
                             f"at batch {state['next']}")
        insert, src, dst, weight = key.stream.ops(key.index)
        dcsr = state["dcsr"]
        report = dcsr.apply(EdgeBatch(np.where(insert, OP_INSERT, OP_DELETE),
                                      src, dst, weight))
        res = run_incremental(dcsr, program, [report], state["values"], state["delta"],
                              source=key.source, config=cfg)
        state.update(values=res.values, delta=res.delta, next=key.index + 1)
        return res

    dcsr = state["dcsr"]
    return run_one, {"delta_csr_s": time.monotonic() - t, "block": dcsr.block_size,
                     "partitions": dcsr.n_partitions, "patch_buckets": lanes}
