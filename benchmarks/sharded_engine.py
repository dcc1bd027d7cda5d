"""Sharded engine groups benchmark: TP execution through the live stack.

Three experiments on >= 8 devices. The benchmark runs on the devices
this process has; on CPU, launch it with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` set by the caller:

1. **Group-size sweep**: greedy decode throughput at TP degree 1/2/4 on
   a colocated engine, with byte-identical token parity asserted against
   the single-device run (the mesh changes placement, never tokens).
2. **Sharded weight sync**: push a new version as per-shard chunks
   through the MooncakeStore and swap it in via ``update_from_chunks``;
   reports chunked-push vs dense-push bytes, swap latency, and the
   no-full-copy accounting — the max per-device param footprint must be
   strictly below the full param footprint (asserted, not just logged).
3. **Unequal PD groups**: a live prefill(TP2) -> decode(TP4) plane runs
   greedy requests to completion with handoff re-sharding, parity
   asserted vs single-device.

    PYTHONPATH=src python -m benchmarks.sharded_engine [--smoke]
"""
from __future__ import annotations

import argparse
import sys
import time

from benchmarks.common import Bench, fmt

NDEV = 8


def run(smoke: bool = False, save: bool = True):
    import jax
    if len(jax.devices()) < NDEV:
        raise RuntimeError(
            f"sharded_engine needs {NDEV} devices, this process has "
            f"{len(jax.devices())}; on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={NDEV}")

    import numpy as np

    from repro.configs import get_config
    from repro.core import build_pd_proxy
    from repro.core.weightstore import (MooncakeStore, pull_param_chunks,
                                        push_params, push_params_sharded)
    from repro.distributed.sharding import model_axis_dims
    from repro.launch.mesh import allocate_engine_devices, make_group_mesh
    from repro.models import Model
    from repro.rl.engine import GenRequest, InferenceEngine

    b = Bench("sharded_engine")
    cfg = get_config("tiny").with_(name="tiny-tp", num_kv_heads=4)
    model = Model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    full_bytes = sum(int(np.asarray(x).nbytes)
                     for x in jax.tree.leaves(params))
    new_tokens = 16 if smoke else 64
    prompts = [[1, 5, 7, 9, 3], [1, 2, 3], [1, 9, 9, 4, 2, 6]]

    def mesh(n):
        return (None if n == 1
                else make_group_mesh(allocate_engine_devices([n])[0]))

    def drive(eng, n_new):
        for i, p in enumerate(prompts):
            eng.add_request(GenRequest(request_id=f"r{i}", prompt=list(p),
                                       max_new_tokens=n_new,
                                       temperature=0.0))
        eng.run_until_idle()
        return [eng.pop_result(f"r{i}").tokens
                for i in range(len(prompts))]

    # --- 1. group-size sweep -------------------------------------------
    ref = None
    for n in (1, 2, 4):
        eng = InferenceEngine(model, params, max_slots=4, max_len=256,
                              mesh=mesh(n))
        drive(eng, 4)                       # warm the jit caches
        eng2 = InferenceEngine(model, params, max_slots=4, max_len=256,
                               mesh=mesh(n))
        t0 = time.time()
        toks = drive(eng2, new_tokens)
        dt = time.time() - t0
        if n == 1:
            ref = toks
        else:
            assert toks == ref, f"TP{n} diverged from single-device greedy"
        dec = eng2.stats()["decode_tokens"]
        b.row(f"tp{n}_decode_tok_s", fmt(dec / max(dt, 1e-9), 1))
        b.row(f"tp{n}_greedy_parity", int(toks == ref), "1")
        if n > 1:
            per_dev = eng2.param_device_bytes()
            b.row(f"tp{n}_max_device_param_mb",
                  fmt(max(per_dev.values()) / 2**20, 3))

    # --- 2. sharded weight sync ----------------------------------------
    params_v1 = model.init(jax.random.PRNGKey(1))
    dims = model_axis_dims(params_v1, 4)
    dense_store = MooncakeStore(bucket_mb=1)
    dense_bytes = push_params(dense_store, params_v1, 1)
    store = MooncakeStore(bucket_mb=1)
    chunk_bytes = push_params_sharded(store, params_v1, 1, 4, dims)
    eng = InferenceEngine(model, params, max_slots=4, max_len=256,
                          mesh=mesh(4))
    drive(eng, 4)                            # in-flight state not needed;
    #                                          warm caches for honest swap
    chunks, version = pull_param_chunks(store, params_v1)
    t0 = time.time()
    eng.update_from_chunks(chunks, version)
    swap_s = time.time() - t0
    per_dev = eng.param_device_bytes()
    assert max(per_dev.values()) < full_bytes, (
        "a device of the TP4 group holds a full param copy: "
        f"{max(per_dev.values())} >= {full_bytes}")
    b.row("param_full_mb", fmt(full_bytes / 2**20, 3))
    b.row("sync_push_dense_mb", fmt(dense_bytes / 2**20, 3))
    b.row("sync_push_chunked_mb", fmt(chunk_bytes / 2**20, 3))
    b.row("sync_swap_s", fmt(swap_s, 4))
    b.row("sync_host_chunk_mb", fmt(eng.stats()["sync_bytes"] / 2**20, 3))
    b.row("tp4_sync_max_device_param_mb",
          fmt(max(per_dev.values()) / 2**20, 3),
          f"< {fmt(full_bytes / 2**20, 3)}")
    b.row("no_full_copy_per_device", 1, "1")

    # --- 3. unequal PD groups ------------------------------------------
    proxy = build_pd_proxy(model, params, max_slots=4, max_len=256,
                           seed=7, prefill_devices_per_engine=2,
                           decode_devices_per_engine=4)
    out = {}
    for i, p in enumerate(prompts):
        proxy.submit(GenRequest(request_id=f"r{i}", prompt=list(p),
                                max_new_tokens=new_tokens,
                                temperature=0.0),
                     callback=lambda r: out.__setitem__(r.request_id, r))
    t0 = time.time()
    pumps = 0
    while proxy.busy:
        proxy.pump()
        pumps += 1
        assert pumps < 20000, "PD plane did not drain"
    dt = time.time() - t0
    toks = [out[f"r{i}"].tokens for i in range(len(prompts))]
    assert toks == ref, "PD(2->4) diverged from single-device greedy"
    st = proxy.stats()
    b.row("pd_2to4_handoffs", st["handoffs"], str(len(prompts)))
    b.row("pd_2to4_greedy_parity", 1, "1")
    b.row("pd_2to4_wall_s", fmt(dt, 2))
    proxy.release_bindings()

    if save:
        b.save()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short decode lengths (CI)")
    args = ap.parse_args(argv)
    run(smoke=args.smoke)
    return 0


if __name__ == "__main__":
    sys.exit(main())
