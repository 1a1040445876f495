"""Serving HA: replica failover, kill/restart, and live-replica restore.

The reference's HA story (`entry/c_api_ha_test.cpp`: forked real server
processes, kill -9 loops while pulls run, restore via replica copy or
reload; `server/EmbeddingRestoreOperator.cpp`) maps here to:

- N REST serving processes sharing a file registry = N replicas; a client
  fails over by retrying the next node (the reference's `pick_one_replica`
  + `Status::NoReplica` retry lives client-side there too).
- A dead node restarts and lazily reloads from the registry.
- A NEW node with no shared filesystem rebuilds the model from a live peer
  via `restore_from_peer` (`:exportmeta`/`:rows`/`:dense` paged endpoints) —
  the reference's coordinated replica-iteration restore.

The in-process test covers the restore protocol end to end; the subprocess
test covers real process death (SIGKILL) and restart.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import openembedding_tpu as embed
from openembedding_tpu.data import synthetic_criteo
from openembedding_tpu.export import StandaloneModel, export_standalone
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import make_deepfm
from openembedding_tpu.serving import (ServingClient, make_server,
                                        restore_from_peer)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SIGN = "ha-model-1"


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    """A small trained DeepFM standalone export + a probe batch."""
    model = make_deepfm(vocabulary=512, dim=8)
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.1))
    batches = list(synthetic_criteo(32, id_space=512, steps=3, seed=3))
    state = trainer.init(batches[0])
    step = trainer.jit_train_step()
    for b in batches:
        state, _ = step(state, b)
    path = str(tmp_path_factory.mktemp("ha") / "export")
    export_standalone(state, model, path, model_sign=SIGN)
    return path, batches[0]


def _http(method, url, body=None, timeout=30):
    data = json.dumps(body).encode() if body is not None else None
    req = urllib.request.Request(url, data=data, method=method)
    if data:
        req.add_header("Content-Type", "application/json")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def _pull_failover(nodes, sign, variable, ids):
    """Replica failover through the shipped client (reference
    `pick_one_replica` + NoReplica-retry semantics, client-side)."""
    return {"weights": ServingClient(nodes).pull(sign, variable, ids).tolist()}


# ---------------------------------------------------------------------------
# in-process: restore protocol end to end
# ---------------------------------------------------------------------------


def test_restore_from_peer_roundtrip(exported, tmp_path):
    path, batch = exported
    reg1 = str(tmp_path / "reg1")
    srv = make_server(reg1)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        peer = f"http://127.0.0.1:{srv.server_address[1]}"
        _http("POST", f"{peer}/models", {"model_sign": SIGN, "model_uri": path})

        ids = [[1, 2], [3, 509]]
        base = _pull_failover([peer], SIGN, "categorical", ids)

        # page size 3 forces multi-page iteration over the hash rows
        dest = restore_from_peer(peer, SIGN, str(tmp_path / "restored"),
                                 page=3)
        restored = StandaloneModel.load(dest)
        got = np.asarray(restored.lookup("categorical", np.asarray(ids)))
        np.testing.assert_allclose(got, np.asarray(base["weights"]),
                                   rtol=0, atol=0)

        # full predict parity through the restored export
        orig = StandaloneModel.load(path)
        bp = {"sparse": {k: v.tolist() for k, v in batch["sparse"].items()},
              "dense": batch["dense"].tolist()}
        a = np.asarray(orig.predict({"sparse": batch["sparse"],
                                     "dense": batch["dense"]}))
        b = np.asarray(restored.predict({"sparse": batch["sparse"],
                                         "dense": batch["dense"]}))
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)

        # guardrails: bad ranges 400, unknown variable 404
        for q, code in ((f"{peer}/models/{SIGN}:rows?var=categorical&start=-1",
                         400),
                        (f"{peer}/models/{SIGN}:rows?var=nope", 404)):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(q, timeout=10)
            assert ei.value.code == code
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# subprocess: real kill -9 / restart (reference c_api_ha_test.cpp shape)
# ---------------------------------------------------------------------------


def _spawn_node(registry, timeout=120):
    env = dict(os.environ, PYTHONPATH=REPO, PYTHONUNBUFFERED="1",
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "openembedding_tpu.serving",
         "--registry", registry, "--port", "0"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    # read on a thread: a wedged child that stays alive without printing must
    # fail this test at `timeout`, not block readline() until the CI job dies
    import queue
    q = queue.Queue()

    def _reader():
        for line in proc.stdout:
            q.put(line)
        q.put(None)

    threading.Thread(target=_reader, daemon=True).start()
    deadline = time.monotonic() + timeout
    seen = []
    while time.monotonic() < deadline:
        try:
            line = q.get(timeout=max(0.1, deadline - time.monotonic()))
        except queue.Empty:
            break
        if line is None:
            break
        seen.append(line)
        if "serving on http://" in line:
            url = line.split("serving on ")[1].split()[0]
            return proc, url
    proc.kill()
    raise AssertionError(f"serving node never came up: {seen[-3:]!r}")


def test_ha_kill_restart_and_peer_restore(exported, tmp_path):
    path, _ = exported
    reg = str(tmp_path / "reg")
    os.makedirs(reg, exist_ok=True)
    ids = [[5, 6, 7]]
    procs = []
    try:
        n1, u1 = _spawn_node(reg)
        procs.append(n1)
        _http("POST", f"{u1}/models", {"model_sign": SIGN, "model_uri": path},
              timeout=120)
        base = _pull_failover([u1], SIGN, "categorical", ids)

        n2, u2 = _spawn_node(reg)
        procs.append(n2)
        # replica 2 serves the same answer from the shared registry
        r2 = _pull_failover([u2], SIGN, "categorical", ids)
        assert r2 == base

        # kill -9 replica 1 mid-service: the client fails over to replica 2
        n1.send_signal(signal.SIGKILL)
        n1.wait(timeout=30)
        r = _pull_failover([u1, u2], SIGN, "categorical", ids)
        assert r == base

        # a NEW node with NO shared filesystem restores from the live peer
        reg2 = str(tmp_path / "reg2")
        dest = restore_from_peer(u2, SIGN, str(tmp_path / "restored2"))
        n3, u3 = _spawn_node(reg2)
        procs.append(n3)
        _http("POST", f"{u3}/models", {"model_sign": SIGN, "model_uri": dest},
              timeout=120)
        r3 = _pull_failover([u3], SIGN, "categorical", ids)
        np.testing.assert_allclose(np.asarray(r3["weights"]),
                                   np.asarray(base["weights"]),
                                   rtol=0, atol=0)

        # the killed node restarts and serves again from the registry
        n1b, u1b = _spawn_node(reg)
        procs.append(n1b)
        r1b = _pull_failover([u1b], SIGN, "categorical", ids)
        assert r1b == base
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def test_restore_refuses_non_normal(exported, tmp_path):
    """restore_from_peer must refuse a model that isn't NORMAL (a CREATING/
    ERROR source would yield a partial or wrong artifact) and surface an
    unknown sign as the peer's 404."""
    import urllib.error

    path, _ = exported
    srv = make_server(str(tmp_path / "regnn"))
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        peer = f"http://127.0.0.1:{srv.server_address[1]}"
        # register as CREATING (never promoted): restore must refuse
        srv.manager.registry.create_model("half-0", path)
        with pytest.raises(RuntimeError, match="CREATING"):
            restore_from_peer(peer, "half-0", str(tmp_path / "d1"))
        with pytest.raises(urllib.error.HTTPError) as ei:
            restore_from_peer(peer, "nope-0", str(tmp_path / "d2"))
        assert ei.value.code == 404
    finally:
        srv.shutdown()
