"""One key at a time through ``run_hytm`` on a runtime built once: the
configuration's ``hytm`` overrides, the traffic's ``program`` with its
``tolerance`` and ``damping`` where the mix gives them."""

import dataclasses
import time


def prepare(config, traffic, edges, jax):
    from repro.core.hytm import HyTMConfig, build_runtime, run_hytm
    from repro.graph.algorithms import ALGORITHMS
    from repro.graph.csr import csr_from_edges

    t = time.monotonic()
    g = csr_from_edges(edges.n, *edges.directed())
    program = dataclasses.replace(ALGORITHMS[traffic["program"]], **{
        k: traffic[k] for k in ("tolerance", "damping") if k in traffic})
    cfg = HyTMConfig(**config["hytm"])
    rt = build_runtime(g, cfg, weighted_norm=program.use_delta and program.weighted)
    jax.block_until_ready((rt.csr, rt.parts))

    def run_one(key):
        return run_hytm(g, program, source=key, config=cfg, runtime=rt)

    return run_one, {"build_runtime_s": time.monotonic() - t,
                     "block": rt.parts.block_size, "partitions": rt.parts.n_partitions}
