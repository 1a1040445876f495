"""TRUE multi-process cluster tests: N OS processes, `jax.distributed` over
gloo, 2 CPU devices per process — the reference's forked-cluster strategy
(`core::MultiProcess`, `entry/c_api_test.h:195,285`) for the machinery that has
multi-host-only code paths:

- `multihost.global_batch` (`jax.make_array_from_process_local_data`),
- `parallel/checkpoint.py` per-process shard writes + cross-process load,
- `persist.AsyncPersister`'s done-marker commit protocol, including the
  crash case (a process dying mid-checkpoint must prevent COMMIT).

The in-process 8-virtual-device suite (`tests/conftest.py`) covers numerics;
these tests cover process boundaries, so they spawn real interpreters (slow:
each pays jax import + compile). The single-process ORACLE comparison runs in
the pytest process itself on its 8 virtual devices — same global devices, same
GSPMD partitioning, so the loss trajectories must agree."""

import json
import os
import socket
import subprocess
import sys

import numpy as np

WORKER = os.path.join(os.path.dirname(__file__), "multiprocess_worker.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(scenario, n, tmp, timeout=420, expect_rc=0, expect_result=True):
    """Run n worker processes to completion; returns the result.json payload.
    `expect_rc=-9` for scenarios that end in a deliberate SIGKILL."""
    port = _free_port()
    env = dict(os.environ)
    # workers never touch an accelerator (a chip belongs to one process at a
    # time) and must not inherit the parent's device-count flags
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    procs = [subprocess.Popen(
        [sys.executable, WORKER, scenario, str(pid), str(n), str(port), tmp],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for pid in range(n)]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=timeout)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == expect_rc, \
            f"worker {pid} rc={p.returncode}\n--- output ---\n{out[-4000:]}"
    if not expect_result:
        return None
    result_path = os.path.join(tmp, "result.json")
    assert os.path.exists(result_path), "process 0 never wrote its result"
    with open(result_path) as f:
        return json.load(f)


def _oracle_losses(steps=4, gb=32):
    """Same training run, single process, same 8 global devices."""
    sys.path.insert(0, os.path.dirname(WORKER))
    try:
        from multiprocess_worker import build_trainer, make_global_batch
    finally:
        sys.path.pop(0)
    import jax
    from openembedding_tpu.parallel import make_mesh, multihost

    mesh = make_mesh()
    trainer = build_trainer(mesh)
    batches = [multihost.global_batch(make_global_batch(s, gb), mesh)
               for s in range(steps)]
    state = trainer.init(batches[0])
    step = trainer.jit_train_step(batches[0], state)
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    return losses


def test_multiprocess_train_and_sharded_checkpoint(tmp_path):
    """4 processes x 2 devices: global-batch assembly, sharded training, and a
    cross-process save_sharded/load_sharded round trip (shard-exact); the loss
    trajectory must match the single-process oracle on the same 8 devices."""
    result = _spawn("train_ckpt", 4, str(tmp_path))
    assert result["ok"] and result["num_processes"] == 4
    assert result["num_devices"] == 8
    oracle = _oracle_losses()
    np.testing.assert_allclose(result["losses"], oracle, rtol=1e-5, atol=1e-6)


def test_multiprocess_persist_commit(tmp_path):
    """2 processes: both write shards + done markers, process 0 commits, and
    the committed persist restores."""
    result = _spawn("persist_ok", 2, str(tmp_path))
    assert result["ok"]
    assert os.path.exists(os.path.join(result["committed"], "COMMIT"))


def test_multiprocess_incremental_persist_sigkill_restore(tmp_path):
    """The reference persists per server node across the cluster
    (`EmbeddingDumpOperator.cpp:36-96`); here: 2 processes train on one mesh,
    each writes its own delta shard files (touched ids unioned across
    processes), every process is SIGKILLed, and FRESH processes restore
    base+deltas bit-exactly — with uncommitted crash junk in the root
    ignored."""
    _spawn("persist_incr_train", 2, str(tmp_path), expect_rc=-9,
           expect_result=False)
    persist_root = os.path.join(str(tmp_path), "persists")
    # the crash junk phase A planted is still there when phase B starts
    assert os.path.isdir(os.path.join(persist_root, "delta_000000000099"))
    result = _spawn("persist_incr_restore", 2, str(tmp_path))
    assert result["ok"] and result["shards_checked"] > 0


def test_multiprocess_incremental_persist_hash_table(tmp_path):
    """Same crash-and-restore story on the HASH-table (hashed 2^40-id) config:
    per-process delta shards carry id-keyed rows, replay re-inserts through
    the sharded find-or-insert kernel, and pulled rows for the touched-id
    union match bit-exactly (slot order may differ; values by id may not)."""
    _spawn("persist_incr_hash_train", 2, str(tmp_path), expect_rc=-9,
           expect_result=False)
    result = _spawn("persist_incr_hash_restore", 2, str(tmp_path))
    assert result["ok"] and result["rows_checked"] > 0


def test_multiprocess_persist_crash_blocks_commit(tmp_path):
    """2 processes: the second dies before writing anything; the commit wait
    must time out (surfaced to the caller) and NO COMMIT marker may exist —
    a restore can never see the partial dump."""
    result = _spawn("persist_kill", 2, str(tmp_path))
    assert result["ok"]
    assert "finished writing" in result["error_surfaced"]
    persist_root = os.path.join(str(tmp_path), "persists")
    if os.path.isdir(persist_root):
        for name in os.listdir(persist_root):
            assert not os.path.exists(
                os.path.join(persist_root, name, "COMMIT"))
