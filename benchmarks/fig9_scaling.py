"""Fig. 9 analogue: RMAT size ladder (CPU-scaled: 0.04M -> 2.5M edges,
64x range like the paper's 0.1B -> 6.4B) — runtime growth of HyTM vs the
single-engine baselines.

``run_devices`` adds the scale-out axis: the same workload swept over
forced-host-platform device counts through the sharded partition sweep
(repro.dist.graph_shard).  Each device count runs in a subprocess because
jax locks the device count at first init.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit, timed
from repro.core.constants import PCIE3
from repro.core.cost_model import COMPACT, FILTER, ZEROCOPY
from repro.core.hytm import HyTMConfig, run_hytm
from repro.graph.algorithms import SSSP
from repro.graph.generators import rmat_graph

LINK = PCIE3.with_(mr=4.0)  # fine transaction groups: avoids ties at CPU scale

SYSTEMS = {"hytm": None, "exptm-f": FILTER, "exptm-c": COMPACT, "imptm-zc": ZEROCOPY}


def run(fast: bool = False):
    sizes = [(2_500, 40_000), (5_000, 160_000), (20_000, 640_000), (40_000, 2_560_000)]
    if fast:
        sizes = sizes[:2]  # 4x edge range instead of 64x
    growth = {}
    for sname, engine in SYSTEMS.items():
        modeled = []
        for n, m in sizes:
            g = rmat_graph(n, m, seed=12)
            cfg = HyTMConfig(link=LINK, n_partitions=max(8, m // 40_000), forced_engine=engine)
            res, wall_us = timed(run_hytm, g, SSSP, source=0, config=cfg, repeats=1)
            modeled.append(res.modeled_seconds)
            emit(f"fig9/{sname}/edges_{m}", wall_us,
                 f"modeled_ms={res.modeled_seconds*1e3:.3f}")
        growth[sname] = modeled[-1] / max(modeled[0], 1e-12)
        span = len(sizes) - 1
        emit(f"fig9/{sname}/growth_{4 ** span}x", 0.0, f"{growth[sname]:.1f}x")
    return growth


_DEVICE_SWEEP_SCRIPT = """
    import time
    import jax
    from repro.core.hytm import HyTMConfig, build_runtime, run_hytm
    from repro.core.constants import PCIE3
    from repro.graph.algorithms import SSSP
    from repro.graph.generators import rmat_graph

    n_dev = len(jax.devices())
    g = rmat_graph({n_nodes}, {n_edges}, seed=12)
    cfg = HyTMConfig(
        link=PCIE3.with_(mr=4.0), n_partitions={n_partitions},
        async_sweep=False, mesh_axis=None if n_dev == 1 else "graph",
    )
    # build the runtime once and reuse it: the warm-up run then leaves a
    # compiled iteration behind for the timed run on both paths
    if cfg.mesh_axis is None:
        rt = build_runtime(g, cfg)
    else:
        from repro.dist.graph_shard import build_sharded_runtime
        from repro.launch.mesh import make_graph_mesh
        rt = build_sharded_runtime(g, cfg, make_graph_mesh())
    run_hytm(g, SSSP, source=0, config=cfg, runtime=rt)   # warm / compile
    t0 = time.monotonic()
    res = run_hytm(g, SSSP, source=0, config=cfg, runtime=rt)
    wall = time.monotonic() - t0
    print(f"RESULT,{{n_dev}},{{wall * 1e6:.1f}},{{res.modeled_seconds * 1e3:.4f}},"
          f"{{res.iterations}},{{res.total_transfer_bytes:.0f}},"
          f"{{res.modeled_ici_seconds * 1e3:.4f}},{{res.total_ici_bytes:.0f}}")

    if n_dev > 1:
        # owner-sharded leg: per-device vertex-state residency drops to
        # the owned slice (+ halo) while the answer stays bit-identical
        import dataclasses
        import numpy as np
        from repro.core.cost_model import vertex_state_bytes
        from repro.core.hytm import HyTMState
        from repro.dist.graph_shard import _owner_place_state

        cfg_o = dataclasses.replace(cfg, vertex_sharding="owner")
        rt_o = build_sharded_runtime(g, cfg_o, rt.mesh)
        run_hytm(g, SSSP, source=0, config=cfg_o, runtime=rt_o)  # warm
        res_o = run_hytm(g, SSSP, source=0, config=cfg_o, runtime=rt_o)
        np.testing.assert_array_equal(res_o.values, res.values)
        assert res_o.iterations == res.iterations
        assert res_o.total_transfer_bytes == res.total_transfer_bytes
        # measured bytes: what each device actually holds for one placed
        # (values, delta, frontier) triple — peak = the max over devices
        st = _owner_place_state(rt_o, SSSP,
                                HyTMState(*SSSP.init_state(g.n_nodes, 0)))
        per_dev = {{}}
        for arr in (st.values, st.delta, st.frontier):
            for sh in arr.addressable_shards:
                d = sh.device.id
                per_dev[d] = per_dev.get(d, 0) + sh.data.nbytes
        measured = max(per_dev.values())
        modeled = vertex_state_bytes(
            g.n_nodes, n_dev, "owner", halo=rt_o.halo.max_halo)
        repl = vertex_state_bytes(g.n_nodes)
        print(f"MEM,{{n_dev}},{{measured}},{{modeled}},{{repl}},"
              f"{{rt_o.halo.max_halo}},{{rt_o.halo.halo_total}}")
"""


def run_devices(device_counts=None, n_nodes=5_000, n_edges=160_000,
                n_partitions=32, fast: bool = False,
                selfcheck: bool = False):
    """Scale-out sweep: one subprocess per forced-host device count, the
    sharded sweep on >1 device (the 1-device row is the single-device
    reference path).  Emits wall time + the modeled transfer metrics,
    which must be device-count-invariant (the model counts bytes, not
    devices) — a cheap end-to-end consistency check on the sharding.

    Multi-device rows also run the owner-sharded leg
    (``vertex_sharding="owner"``): the subprocess asserts bit-identity
    with the replicated run and reports per-device peak vertex-state
    bytes — measured from the placed arrays' addressable shards — plus
    the modeled owned-slice + halo bytes
    (``cost_model.vertex_state_bytes``).  ``selfcheck`` gates the
    ~``n/D`` scaling: each device may hold at most its padded owned
    slice, a D-fold drop from the replicated ``9n``-byte ceiling."""
    if device_counts is None:
        # --fast trims only the *default* sweep; an explicit device list
        # (e.g. the CI 16-device owner-sharding gate) runs as given,
        # still on the shrunken fast graph
        device_counts = (1, 2) if fast else (1, 2, 4, 8)
    if fast:
        n_nodes, n_edges = min(n_nodes, 2_000), min(n_edges, 40_000)
    from repro.launch.mesh import forced_host_device_env

    script = textwrap.dedent(
        _DEVICE_SWEEP_SCRIPT.format(
            n_nodes=n_nodes, n_edges=n_edges, n_partitions=n_partitions
        )
    )
    rows = {}
    mem = {}
    for n_dev in device_counts:
        out = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=600,
            env=forced_host_device_env(n_dev),
        )
        if out.returncode != 0:
            raise SystemExit(
                f"fig9 device sweep failed on {n_dev} device(s):\n"
                f"{out.stderr[-3000:]}")
        line = [l for l in out.stdout.splitlines() if l.startswith("RESULT,")][0]
        _, dev, wall_us, modeled_ms, iters, bytes_, ici_ms, ici_bytes = line.split(",")
        rows[n_dev] = float(modeled_ms)
        # two-level transfer management: the PCIe/HBM level (modeled_ms,
        # device-count-invariant) + the cross-device merge charged over
        # the ICI link (grows with the device count)
        emit(
            f"fig9/devices_{n_dev}", float(wall_us),
            f"modeled_ms={modeled_ms} iters={iters} bytes={bytes_} "
            f"ici_ms={ici_ms} ici_bytes={ici_bytes}",
        )
        for mline in out.stdout.splitlines():
            if not mline.startswith("MEM,"):
                continue
            _, _, measured, modeled, repl, max_halo, halo_total = \
                mline.split(",")
            mem[n_dev] = (int(measured), int(modeled), int(repl),
                          int(max_halo), int(halo_total))
            emit(
                f"fig9/devices_{n_dev}/owner_state_bytes", 0.0,
                f"measured={measured} modeled={modeled} replicated={repl} "
                f"max_halo={max_halo} halo_total={halo_total}",
            )
    if selfcheck:
        _selfcheck_owner_memory(mem, n_nodes, device_counts)
    return rows


def _selfcheck_owner_memory(mem: dict, n_nodes: int,
                            device_counts) -> None:
    """The owner-sharding memory gate: every multi-device row must have
    produced its MEM record (the subprocess already asserted
    bit-identity before printing it), measured per-device state bytes
    must equal the padded owned slice — a ~D-fold drop from the
    replicated 9n ceiling — and the modeled total must be owned slice +
    halo, with the halo a strict subset of the non-owned vertices."""
    from repro.core.cost_model import STATE_BYTES_PER_VERTEX

    expected = [d for d in device_counts if d > 1]
    missing = [d for d in expected if d not in mem]
    if missing:
        raise AssertionError(
            f"owner-sharding selfcheck: no MEM record for device counts "
            f"{missing} — the owner leg did not run")
    for n_dev, (measured, modeled, repl, max_halo, halo_total) in mem.items():
        n_loc = -(-n_nodes // n_dev)
        owned = STATE_BYTES_PER_VERTEX * n_loc
        if measured != owned:
            raise AssertionError(
                f"devices={n_dev}: measured per-device state bytes "
                f"{measured} != owned-slice bytes {owned} (~n/D scaling "
                f"violated)")
        if measured * n_dev > repl + STATE_BYTES_PER_VERTEX * n_dev:
            raise AssertionError(
                f"devices={n_dev}: owner layout total {measured * n_dev} "
                f"exceeds replicated-per-device {repl} + padding")
        if modeled != owned + STATE_BYTES_PER_VERTEX * max_halo:
            raise AssertionError(
                f"devices={n_dev}: modeled bytes {modeled} != owned "
                f"{owned} + halo {STATE_BYTES_PER_VERTEX * max_halo}")
        if not 0 <= max_halo <= n_loc * n_dev - n_loc:
            raise AssertionError(
                f"devices={n_dev}: max_halo {max_halo} outside "
                f"[0, n_pad - n_loc]")
    print(f"OK fig9-devices owner-memory selfcheck: "
          f"{sorted(mem)} device counts, per-device state bytes = "
          f"9*ceil(n/D) each")


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--selfcheck", action="store_true",
                    help="gate the owner-sharded ~n/D per-device "
                         "state-byte scaling (runs the device sweep only)")
    ap.add_argument("--devices", type=int, nargs="*", default=None,
                    help="device counts for the scale-out sweep")
    args = ap.parse_args()
    kw = {}
    if args.devices:
        kw["device_counts"] = tuple(args.devices)
    if args.selfcheck:
        run_devices(fast=args.fast, selfcheck=True, **kw)
    else:
        run(fast=args.fast)
        run_devices(fast=args.fast, **kw)
