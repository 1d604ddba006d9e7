"""Worker for the REAL 2-process distributed test (test_distributed.py).

Run as a subprocess (one per process rank) with JAX_PLATFORMS=cpu and
XLA_FLAGS=--xla_force_host_platform_device_count=2 in the environment:

    python tests/multiproc_worker.py <rank> <nprocs> <port> <out_dir> [max_chunks]

Joins a localhost coordinator via the package's own ``initialize_distributed``,
builds the slice-aware multi-host stage mesh (data axis spanning the two
processes), and runs a tiny split eval whose per-example NLLs are sharded
across processes — executing, not mocking, ``fetch_global``'s
``process_allgather`` branch and the process-0-only checkpoint writes. Every
rank writes its final result dict to ``out_dir/result_<rank>.json``; under
SPMD all ranks must agree, and the parent test compares rank files to each
other and to a single-process run.
"""
import json
import os
import sys

import numpy as np

import jax

jax.config.update("jax_platforms", "cpu")
# cross-process CPU collectives (the ICI/DCN analogue in this test rig)
jax.config.update("jax_cpu_collectives_implementation", "gloo")
from edgellm_tpu.utils.startup import configure_compile_cache

configure_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def workload():
    """The shared tiny split-eval workload: (cfg_kwargs, corpus_seed_len,
    run_split_eval kwargs). One definition for both the subprocess workers and
    the parent test's single-process oracle, so they cannot drift."""
    cfg_kwargs = dict(num_layers=4, hidden_size=32, num_heads=4, vocab_size=128)
    run_kwargs = dict(cuts=(1,), hop_codecs=("int4_per_token",), max_length=16,
                      stride=8, time_hops=False)
    return cfg_kwargs, (7, 16 + 8 * 6), run_kwargs


def main():
    rank, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    out_dir = sys.argv[4]
    max_chunks = int(sys.argv[5]) if len(sys.argv) > 5 else None

    from edgellm_tpu.parallel import (initialize_distributed,
                                      make_multihost_stage_mesh)

    n = initialize_distributed(coordinator_address=f"localhost:{port}",
                               num_processes=nprocs, process_id=rank)
    assert n == nprocs, f"expected {nprocs} processes, initialize returned {n}"
    assert jax.process_count() == nprocs
    assert len(jax.devices()) == nprocs * len(jax.local_devices())

    from edgellm_tpu.models import tiny_config, init_params
    from edgellm_tpu.eval.split_eval import run_split_eval

    # stage axis within a process, data axis across the two processes
    mesh = make_multihost_stage_mesh(2, n_data=nprocs, n_model=1)
    by_proc = {d.process_index for d in
               np.asarray(mesh.devices)[:, 0, :].ravel()}
    assert len(by_proc) == 1, "a stage group spans processes"

    cfg_kwargs, (seed, length), run_kwargs = workload()
    cfg = tiny_config("qwen2", **cfg_kwargs)
    params = init_params(cfg, jax.random.key(0))  # identical on every rank
    corpus = np.random.default_rng(seed).integers(0, cfg.vocab_size, length)

    result = run_split_eval(
        cfg, params, corpus, mesh=mesh, window_batch=nprocs,
        max_chunks=max_chunks,
        checkpoint_path=os.path.join(out_dir, "ckpt.json"),
        checkpoint_every=1,
        metrics_path=os.path.join(out_dir, "metrics.jsonl"), **run_kwargs)

    with open(os.path.join(out_dir, f"result_{rank}.json"), "w") as f:
        json.dump({k: v for k, v in result.items()
                   if isinstance(v, (int, float, str, list))}, f)
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()
