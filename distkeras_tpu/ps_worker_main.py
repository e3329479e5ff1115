"""Standalone parameter-server worker process — the DCN executor.

The reference ran each worker closure in a Spark *executor process* on
another machine, dialing back to the driver's socket PS (reference:
``distkeras/workers.py`` shipped via ``rdd.mapPartitionsWithIndex`` —
SURVEY.md §3.1).  This module is that executor for the TPU rebuild: a
process entry point that loads its shard + model blob from disk, connects
to the PS over TCP, trains with the jitted window loop, and writes its
history back for the driver to collect.

Launched by ``parameter_servers.run_process_ps_training`` through
``job_deployment.Job`` — ``LocalJobRunner`` for same-host processes (the
cross-process test path), ``SSHJobRunner`` for real multi-host DCN
deployments.  The worker id comes from the ``DISTKERAS_TPU_PROCESS_ID``
env var ``Job.host_env`` renders, and ``initialize_from_env()`` runs first
so a deployment that also wants a jax.distributed mesh in the workers gets
it from the same env contract.

Usage: ``python -m distkeras_tpu.ps_worker_main <config.json>``
"""

from __future__ import annotations

import json
import os
import sys


def load_model_blob(path: str) -> dict:
    """Read a {'model': json, 'weights': [...]} blob from disk — one codec
    for the framework (``core.model``'s npz layout), no re-trace."""
    from .core.model import read_npz_blob
    return read_npz_blob(path)


def save_model_blob(path: str, blob: dict) -> None:
    from .core.model import write_npz_blob
    write_npz_blob(path, blob)


def main(argv=None) -> int:
    argv = sys.argv if argv is None else argv
    if len(argv) not in (2, 3):
        print("usage: python -m distkeras_tpu.ps_worker_main <config.json> "
              "[worker_id]", file=sys.stderr)
        return 2
    from .job_deployment import initialize_from_env
    initialize_from_env()

    import numpy as np

    from .workers import WORKER_CLASSES

    with open(argv[1]) as f:
        cfg = json.load(f)
    # argv wins over the env slot: a supervisor respawning ONE worker under
    # a fresh id appends it to the same config's argv
    if len(argv) == 3:
        worker_id = int(argv[2])
    else:
        worker_id = int(os.environ.get("DISTKERAS_TPU_PROCESS_ID",
                                       cfg.get("worker_id", 0)))

    blob = load_model_blob(cfg["model_path"])

    optimizer = cfg["worker_optimizer"]
    if isinstance(optimizer, dict):  # Optimizer.get_config round-trip
        from .core.optimizers import Optimizer
        optimizer = Optimizer(**optimizer)

    # the config is _worker_kwargs' output plus transport keys: pass the
    # kwargs through verbatim so a kwarg added there reaches the child
    # without this module re-enumerating the list (rho is present exactly
    # when the worker class accepts it)
    transport = {"algorithm", "model_path", "shard_paths", "result_paths",
                 "worker_optimizer", "worker_id", "num_shards",
                 "shard_addrs", "lease_host", "lease_port", "data_path",
                 "result_dir"}
    kw = {k: v for k, v in cfg.items() if k not in transport}

    # sharded PS: rebuild the deterministic shard plan from the blob (same
    # (shapes, dtypes, num_shards) → same plan as the driver's) and hand the
    # worker the pinned shard addresses — same-address respawn means these
    # stay valid across a PS shard death
    if int(cfg.get("num_shards", 1)) > 1:
        from .ps_sharding import make_shard_plan
        weights = [np.asarray(w) for w in blob["weights"]]
        kw["shard_plan"] = make_shard_plan(
            [w.shape for w in weights], [w.dtype for w in weights],
            int(cfg["num_shards"]))
        kw["shard_addrs"] = [(str(h), int(p))
                             for h, p in cfg["shard_addrs"]]

    worker_cls = WORKER_CLASSES[cfg["algorithm"]]
    worker = worker_cls(blob, worker_optimizer=optimizer, **kw)

    if cfg.get("lease_port"):
        # elastic mode: no static shard — lease row ranges of the full
        # dataset from the driver's LeaseServer, epoch by epoch, exactly
        # like the in-process elastic engine's run_fn
        from .resilience import LeaseClient
        with np.load(cfg["data_path"]) as z:
            x, y = z["x"], z["y"]
        client = LeaseClient(cfg.get("lease_host", "127.0.0.1"),
                             int(cfg["lease_port"]))
        state, last = None, None
        try:
            client.connect()
            while True:
                epoch = client.wait_epoch(last)
                if epoch is None:
                    break
                last = epoch
                # the driver's global shuffle, reproduced bit for bit: the
                # lease's row range indexes the same permutation everywhere
                perm = np.random.default_rng(
                    worker.seed + 7919 * epoch).permutation(len(x))
                xe, ye = x[perm], y[perm]

                def data_fn(lease):
                    return (xe[lease.start:lease.stop],
                            ye[lease.start:lease.stop])

                res = worker.train_leases(worker_id, client, data_fn,
                                          initial_state=state)
                state = res["state"]
        finally:
            client.close()
        out = os.path.join(cfg["result_dir"], f"result_{worker_id}.npz")
        np.savez(out, history=np.asarray(worker.history, np.float32))
        return 0

    with np.load(cfg["shard_paths"][worker_id]) as z:
        shard = {cfg["features_col"]: z["x"], cfg["label_col"]: z["y"]}
    result = worker.train(worker_id, shard)
    np.savez(cfg["result_paths"][worker_id],
             history=np.asarray(result["history"], np.float32))
    return 0


if __name__ == "__main__":
    sys.exit(main())
