"""Serving: model registry, model manager, REST admin + inference HTTP server.

Reference parity map (SURVEY.md §2.3/§2.4, §3.5):
- master KV tree `_hyper-embedding-model_` + ModelMeta status protocol
  (`client/Connection.cpp:214-277`, `variable/Meta.h`) -> file-based `ModelRegistry`
  (atomic JSON writes; one registry dir replaces the master process).
- `ModelManager::find_model_variable` (`client/ModelController.cpp:24-44`: cache by
  model_sign, refuse CREATING, read-only handles) -> `ModelManager`.
- controller binary REST API (`entry/controller.cc:100-205`: POST/GET/DELETE /models,
  GET/DELETE /nodes) -> `ServingHandler` routes, same resources.
- TF-Serving `PullWeights` serving path with `model_sign = uuid + "-" +
  floor(model_version)` (`tensorflow/exb_ops.cpp:261-276`, `entry/py_api.cc:130-138`)
  -> `resolve_sign` + POST /models/<sign>/pull.

Training-side HA (replica shards, dead-node restore) is obviated by SPMD training;
serving HA maps to running N of these servers behind a load balancer, each loading the
same export — the registry is just files, so replicas share it read-only.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Dict, Optional

import numpy as np

from .export import StandaloneModel
from .utils import trace
from .utils.trace import REQUEST_ID_HEADER


class _BadRequest(Exception):
    """Client sent a syntactically/semantically invalid request body (-> 400)."""

MODEL_STATUS = ("CREATING", "NORMAL", "DELETING", "ERROR")


def _ids_array(v, *, pooled: bool = False) -> np.ndarray:
    """Sparse-id JSON payload -> int64 array.

    `pooled=True` — every spec consuming this feature has a combiner, so the
    field width is free: RAGGED lists of id lists (the natural client
    encoding for multivalent features) pad to the next power-of-two width
    with -1 (pad slots pull zero rows and pooling masks them out,
    `embedding.combine`), rectangular input width-buckets the same way so
    the jit compile cache stays O(log max_width) programs per feature
    (`export.bucket_size`, floor 1), and 1-D input rank-expands to one-id
    lists (Keras fit's convention, mirrored by `inject`).

    `pooled=False` — the model's field count is part of its architecture
    (e.g. DeepFM's 26 columns): the strict rectangular contract stays, and a
    ragged payload raises (-> the caller's 400). Padding here would fabricate
    zero rows into the tower — a silently wrong 200."""
    from .data import is_ragged
    from .export import bucket_size
    if not pooled:
        return np.asarray(v, dtype=np.int64)
    if is_ragged(v):
        return _pad_ragged_bucketed(v)
    ids = np.asarray(v, dtype=np.int64)
    if ids.ndim == 1:
        return ids[:, None]
    if ids.ndim == 2:
        b = bucket_size(ids.shape[-1], floor=1)
        if ids.shape[-1] != b:
            ids = np.pad(ids, [(0, 0), (0, b - ids.shape[-1])],
                         constant_values=-1)
    return ids


def _pad_ragged_bucketed(v) -> np.ndarray:
    """The one ragged-padding policy for serving endpoints: pad to the next
    power-of-two field width with -1 (`export.bucket_size`, floor 1)."""
    from .data import pad_ragged
    from .export import bucket_size
    return pad_ragged(v, width=bucket_size(max(len(s) for s in v), floor=1))


def _pull_ids(v) -> np.ndarray:
    """Pull-endpoint ids: ragged lists pad to the power-of-two width (the
    caller reads pad rows back as zeros — shape-explicit); rectangular input
    passes through UNCHANGED so the response mirrors the requested shape."""
    from .data import is_ragged
    if is_ragged(v):
        return _pad_ragged_bucketed(v)
    return np.asarray(v, dtype=np.int64)


def _pooled_features(servable) -> set:
    """Feature names whose consuming specs ALL pool (combiner set) — the
    features whose width is free at serving time. Specs come from either
    servable kind; unknown specs (recipe-less standalone export) -> empty set
    (strict coercion everywhere). Memoized on the servable (immutable per
    load, and this sits on the predict hot path)."""
    cached = getattr(servable, "_pooled_features_cache", None)
    if cached is not None:
        return cached
    specs = getattr(servable, "specs", None)
    if not isinstance(specs, dict):
        m = getattr(servable, "model", None)
        specs = m.specs if m is not None else {}
    by_feature = {}
    for s in specs.values():
        by_feature.setdefault(s.feature_name, []).append(s)
    out = {f for f, ss in by_feature.items() if all(x.combiner for x in ss)}
    try:
        servable._pooled_features_cache = out
    except AttributeError:  # __slots__ servables: recompute per request
        pass
    return out


def resolve_sign(uuid: str, model_version: float) -> str:
    """uuid + "-" + floor(version) (reference `py_api.cc:130-138`)."""
    return f"{uuid}-{int(math.floor(model_version))}"


class ModelRegistry:
    """File-backed model registry: one JSON per model_sign under <root>/models/.

    Writes are atomic (tmp + rename), so concurrent serving replicas reading the same
    directory never see torn state — the moral equivalent of the reference's master
    tree KV + lock (`Connection.cpp:214-277`)."""

    def __init__(self, root: str):
        self.root = root
        self._dir = os.path.join(root, "models")
        os.makedirs(self._dir, exist_ok=True)
        self._lock = threading.Lock()

    def _path(self, sign: str) -> str:
        if not re.fullmatch(r"[A-Za-z0-9._-]+", sign):
            raise ValueError(f"bad model sign {sign!r}")
        return os.path.join(self._dir, f"{sign}.json")

    def _write(self, sign: str, entry: dict) -> None:
        path = self._path(sign)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(entry, f, indent=2, sort_keys=True)
        os.replace(tmp, path)

    def create_model(self, model_sign: str, uri: str, *, replica_num: int = 1,
                     shard_num: int = 1) -> dict:
        """Register CREATING -> caller loads/validates -> mark NORMAL.
        An existing CREATING entry is overwritten (the reference handles interrupted
        CREATING the same way, `ModelController.cpp:47-85`); NORMAL entries refuse.

        `shard_num` selects the servable kind (1 = materialized StandaloneModel,
        >1 = ShardedModel over that many devices — `ModelManager._load_entry`).
        `replica_num` is DECLARATIVE here: replicas are serving processes the
        operator runs (each node that loads this entry is one replica;
        `ServingClient` fails over between them), unlike the reference where
        the PS itself places replica_num copies of each shard
        (`Model.cpp:153-186`). The field records intent for operators/tooling;
        this registry does not spawn processes."""
        with self._lock:
            cur = self.get(model_sign)
            if cur is not None and cur.get("status") == "NORMAL":
                raise FileExistsError(f"model {model_sign!r} already exists")
            entry = {"model_sign": model_sign, "uri": uri,
                     "replica_num": replica_num, "shard_num": shard_num,
                     "status": "CREATING", "error": "",
                     "create_time": time.time()}
            self._write(model_sign, entry)
            return entry

    def set_status(self, model_sign: str, status: str, error: str = "") -> dict:
        if status not in MODEL_STATUS:
            raise ValueError(f"bad status {status!r}")
        with self._lock:
            entry = self.get(model_sign)
            if entry is None:
                raise KeyError(model_sign)
            entry["status"] = status
            entry["error"] = error
            self._write(model_sign, entry)
            return entry

    def delete_model(self, model_sign: str) -> None:
        with self._lock:
            path = self._path(model_sign)
            if not os.path.exists(path):
                raise KeyError(model_sign)
            os.unlink(path)

    def get(self, model_sign: str) -> Optional[dict]:
        try:
            with open(self._path(model_sign)) as f:
                return json.load(f)
        except FileNotFoundError:
            return None

    def show_models(self) -> Dict[str, dict]:
        out = {}
        for fn in sorted(os.listdir(self._dir)):
            if fn.endswith(".json"):
                with open(os.path.join(self._dir, fn)) as f:
                    entry = json.load(f)
                out[entry["model_sign"]] = entry
        return out


class ModelManager:
    """model_sign -> cached servable; refuses models not in NORMAL state
    (reference `ModelManager::find_model_variable`, `ModelController.cpp:24-44`).

    `shard_num == 1` loads a materialized `StandaloneModel` (export layout,
    small models). `shard_num > 1` loads the model SHARDED over `shard_num`
    devices straight from a (sharded) checkpoint — never materialized in one
    place (`parallel/serving.ShardedModel`) — the reference's serving-from-
    the-sharded-PS path (`exb_ops.cpp:261-276`)."""

    def __init__(self, registry: ModelRegistry):
        self.registry = registry
        # the RCU servable cache: swap/evict/load publish through it, every
        # predict resolves from it (lock discipline enforced by `make lint`,
        # tools/oelint lockset pass)
        self._cache: Dict[str, object] = {}  # guarded-by: self._lock
        self._lock = threading.Lock()
        # per-sign load guards: two first requests racing for the same model
        # must not both run a (device-memory-heavy) sharded load
        self._loading: Dict[str, threading.Lock] = {}  # guarded-by: self._lock

    @staticmethod
    def _load_entry(entry: dict):
        shard_num = int(entry.get("shard_num", 1))
        if shard_num <= 1:
            return StandaloneModel.load(entry["uri"])
        import jax
        from .parallel.mesh import make_mesh
        from .parallel.serving import ShardedModel
        devices = jax.devices()
        if shard_num > len(devices):
            raise ValueError(
                f"shard_num={shard_num} exceeds the {len(devices)} devices "
                "on this serving node")
        return ShardedModel.load(entry["uri"],
                                 mesh=make_mesh(devices[:shard_num]))

    def find_model(self, model_sign: str):
        with self._lock:
            if model_sign in self._cache:
                return self._cache[model_sign]
            guard = self._loading.setdefault(model_sign, threading.Lock())
        with guard:
            with self._lock:  # the winner may have finished while we waited
                if model_sign in self._cache:
                    return self._cache[model_sign]
            entry = self.registry.get(model_sign)
            if entry is None:
                raise KeyError(f"unknown model {model_sign!r}")
            if entry["status"] != "NORMAL":
                raise RuntimeError(
                    f"model {model_sign!r} is {entry['status']}, not servable")
            loaded = self._load_entry(entry)
            with self._lock:
                self._cache[model_sign] = loaded
            return loaded

    def servable_versions(self) -> Dict[str, dict]:
        """{sign: {step, kind}} of every LOADED servable (the /statusz view —
        the registry shows what's registered, this shows what's resident)."""
        with self._lock:
            cache = dict(self._cache)
        return {sign: {"step": int(getattr(m, "step", 0) or 0),
                       "kind": type(m).__name__}
                for sign, m in cache.items()}

    def find_model_variable(self, model_sign: str, variable: str):
        m = self.find_model(model_sign)
        if variable not in m.variable_names:
            raise KeyError(f"model {model_sign!r} has no variable {variable!r}")
        return m, variable

    def evict(self, model_sign: str) -> None:
        with self._lock:
            self._cache.pop(model_sign, None)

    def swap(self, model_sign: str, servable, *, expected=None) -> None:
        """RCU publish of a new servable version (online sync,
        `sync/subscriber.py`): requests that already resolved the old object
        finish on it; the next `find_model` returns the new one. With
        `expected`, the swap is conditional — it refuses when the cached
        servable is no longer the one the update was derived from (a
        concurrent reload/delete won the race; the subscriber re-syncs from
        the fresh servable's version instead of clobbering it)."""
        with self._lock:
            cur = self._cache.get(model_sign)
            if cur is None:
                raise KeyError(
                    f"model {model_sign!r} is not loaded; cannot swap")
            if expected is not None and cur is not expected:
                raise RuntimeError(
                    f"model {model_sign!r} was reloaded concurrently; "
                    "swap abandoned")
            self._cache[model_sign] = servable
        trace.event("serving", "servable_swap", model=model_sign,
                    step=int(getattr(servable, "step", 0) or 0))

    def load_model(self, model_sign: str, uri: str, *, replica_num: int = 1,
                   shard_num: int = 1) -> dict:
        """create_model + validate-load + NORMAL/ERROR transition (the controller's
        create flow, `ModelController.cpp:47-85`, done synchronously)."""
        entry = self.registry.create_model(model_sign, uri,
                                           replica_num=replica_num,
                                           shard_num=shard_num)
        try:
            loaded = self._load_entry(entry)
            with self._lock:
                self._cache[model_sign] = loaded
            return self.registry.set_status(model_sign, "NORMAL")
        except Exception as e:  # noqa: BLE001 - status must record any failure
            self.registry.set_status(model_sign, "ERROR", error=str(e))
            raise


# ---------------------------------------------------------------------------
# REST server (controller + inference parity in one process)
# ---------------------------------------------------------------------------


class ServingHandler(BaseHTTPRequestHandler):
    manager: ModelManager = None  # set by make_server
    batcher: "Optional[MicroBatcher]" = None  # set when batching is enabled
    # model_sign -> publisher/subscriber registries: DELIBERATE class-level
    # shared state — http.server constructs one handler INSTANCE per request,
    # so per-server mutable registries must live on the per-server Handler
    # subclass (make_server assigns fresh dicts; POST publish/sync mutates
    # them across requests by design)
    # oelint: disable=lockset -- per-server registry; make_server subclass gets a fresh dict
    publishers: dict = {}   # model_sign -> sync.SyncPublisher (make_server)
    # oelint: disable=lockset -- per-server registry; make_server subclass gets a fresh dict
    subscribers: dict = {}  # model_sign -> sync.SyncSubscriber (make_server)
    # read-only defaults: make_server replaces these on the subclass; the
    # immutable peers tuple means a stray bare-ServingHandler append fails
    peers: tuple = ()       # default /fleetz scrape set (make_server/--peers)
    # oelint: disable=lockset -- read-only default; make_server assigns a fresh dict per server
    node_info: dict = {}
    quiet = True

    # -- plumbing -----------------------------------------------------------

    def log_message(self, fmt, *args):
        if not self.quiet:
            super().log_message(fmt, *args)

    def send_response(self, code, message=None):
        """Every response echoes the request id (`X-OETPU-Request-Id`),
        stamps this node's wall clock (`X-OETPU-Server-Time`, the Cristian
        clock-offset probe clients read), and records the status onto the
        request's http span."""
        super().send_response(code, message)
        rid = getattr(self, "_request_id", None)
        if rid:
            self.send_header(REQUEST_ID_HEADER, rid)
        self.send_header(trace.SERVER_TIME_HEADER, repr(time.time()))
        sp = getattr(self, "_http_span", None)
        if sp is not None:
            sp.attrs["status"] = int(code)

    def _traced(self, method: str, handler):
        """Trace-context middleware: adopt the client's `X-OETPU-Trace`
        context (falling back to `X-OETPU-Request-Id`, generating an id when
        absent), bind it for the request's lifetime, and wrap the whole
        handler in the root `serving.http` span — every nested span (predict,
        queue wait, batch exec, model call; publisher-side delta serves in a
        sync round) correlates by this id, and the http span's
        `remote_parent` links it under the CALLER's span across the process
        boundary (the stitched fleet trace tree)."""
        ctx = trace.extract_context(self.headers)
        rid = (ctx.trace_id if ctx is not None else None) \
            or trace.new_request_id()
        self._request_id = rid
        with trace.request(rid, remote_parent=ctx.parent_span
                           if ctx is not None else None):
            with trace.span("serving", "http", method=method,
                            path=self.path) as sp:
                self._http_span = sp
                return handler()

    def _json(self, code: int, payload, headers: Optional[dict] = None) -> None:
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _blob(self, body: bytes, headers: Optional[dict] = None) -> None:
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(body)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def _body(self) -> dict:
        length = int(self.headers.get("Content-Length", 0))
        if length == 0:
            return {}
        data = json.loads(self.rfile.read(length))
        if not isinstance(data, dict):
            raise _BadRequest("request body must be a JSON object")
        return data

    def _route(self):
        from urllib.parse import parse_qs, urlsplit
        parts = urlsplit(self.path)
        self.query = {k: v[-1] for k, v in parse_qs(parts.query).items()}
        path = parts.path.rstrip("/")
        m = re.fullmatch(
            r"/models/([A-Za-z0-9._-]+)/delta/(\d+)"
            r"/(meta|dense|table/[A-Za-z0-9._-]+)", path)
        if m:
            return "delta", m.group(1), (int(m.group(2)), m.group(3))
        m = re.fullmatch(r"/models/([A-Za-z0-9._-]+)"
                         r"(?::(\w+)|/(pull|predict|publish|sync))?",
                         path)
        if m:
            return "model", m.group(1), m.group(2) or m.group(3)
        if path == "/models":
            return "models", None, None
        m = re.fullmatch(r"/nodes/([A-Za-z0-9._-]+)", path)
        if m:
            return "node", m.group(1), None
        if path == "/nodes":
            return "nodes", None, None
        if path == "/healthz":
            return "healthz", None, None
        if path == "/metrics":
            return "metrics", None, None
        if path == "/fleetz":
            return "fleetz", None, None
        if path == "/statusz":
            return "statusz", None, None
        if path == "/tracez":
            return "tracez", None, None
        if path == "/sloz":
            return "sloz", None, None
        if path == "/historz":
            return "historz", None, None
        if path == "/timelinez":
            return "timelinez", None, None
        if path == "/capsule":
            return "capsule", None, None
        return None, None, None

    # -- verbs --------------------------------------------------------------

    def _npz(self, arrays: dict) -> None:
        """Stream a dict of numpy arrays as an uncompressed .npz body."""
        import io
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        body = buf.getvalue()
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _text(self, body: str, code: int = 200) -> None:
        raw = body.encode()
        self.send_response(code)
        self.send_header("Content-Type", "text/plain; charset=utf-8")
        self.send_header("Content-Length", str(len(raw)))
        self.end_headers()
        self.wfile.write(raw)

    def _statusz_text(self) -> str:
        """Operator one-pager: build/config, servable versions, sync state
        (with the last DEGRADED reason), publishers, flight-recorder tail."""
        import platform
        lines = ["== openembedding_tpu serving /statusz =="]
        build = {"python": platform.python_version()}
        try:
            import jax
            build["jax"] = jax.__version__
        except Exception:  # noqa: BLE001 — statusz must render regardless
            pass
        lines.append("build: " + " ".join(f"{k}={v}"
                                          for k, v in build.items()))
        lines.append("node: " + json.dumps(self.node_info, sort_keys=True))
        lines.append("")
        lines.append("-- servables (loaded) --")
        versions = self.manager.servable_versions()
        if not versions:
            lines.append("(none loaded)")
        for sign, v in sorted(versions.items()):
            entry = self.manager.registry.get(sign) or {}
            lines.append(f"{sign}: step={v['step']} kind={v['kind']} "
                         f"status={entry.get('status', '?')}")
        lines.append("")
        lines.append("-- sync subscribers --")
        if not self.subscribers:
            lines.append("(none)")
        for sign, sub in sorted(self.subscribers.items()):
            st = sub.status()
            f = st.get("freshness_ms")
            fresh = f"freshness_ms={f:.1f} " if f is not None else ""
            lines.append(
                f"{sign}: state={st['state']} version={st['version']} "
                f"applied={st['applied']} {fresh}"
                f"last_degraded_reason={st.get('last_degraded_reason')}")
        lines.append("")
        lines.append("-- sync publishers --")
        if not self.publishers:
            lines.append("(none)")
        for sign, pub in sorted(self.publishers.items()):
            try:
                feed = pub.versions()
                lines.append(f"{sign}: head_step={feed['head_step']} "
                             f"base_step={feed['base_step']} "
                             f"deltas={len(feed['deltas'])}")
            except Exception as e:  # noqa: BLE001
                lines.append(f"{sign}: (feed error: {e})")
        lines.append("")
        lines.append("-- workload skew (hot ids) --")
        from .utils import sketch
        lines.append(sketch.MONITOR.render_text(
            top=int(self.query.get("top", 8)) if hasattr(self, "query")
            else 8))
        lines.append("")
        lines.append("-- placement (self-driving) --")
        try:
            from .placement.controller import render_status
            lines.append(render_status())
        except Exception as e:  # noqa: BLE001 — statusz must render regardless
            lines.append(f"(placement status unavailable: {e})")
        lines.append("")
        lines.append("-- SLOs (GET /sloz for JSON) --")
        try:
            from .utils import slo
            slo.EVALUATOR.evaluate_now()
            lines.append(slo.EVALUATOR.render_text())
        except Exception as e:  # noqa: BLE001 — statusz must render regardless
            lines.append(f"(slo status unavailable: {e})")
        lines.append("")
        lines.append("-- ingest (line-rate) --")
        try:
            from .utils import metrics as metrics_mod
            ingest = {k: v for k, v in metrics_mod.report(reset=False).items()
                      if k.startswith("ingest.")
                      and not k.endswith((".p50", ".p95", ".p99"))}
            lines.append(metrics_mod._format_table(ingest)
                         if ingest else "(no ingest activity)")
        except Exception as e:  # noqa: BLE001 — statusz must render regardless
            lines.append(f"(ingest status unavailable: {e})")
        lines.append("")
        lines.append("-- metric history (GET /historz for JSON) --")
        try:
            from .utils import history
            lines.append(history.render_sparklines())
        except Exception as e:  # noqa: BLE001 — statusz must render regardless
            lines.append(f"(history unavailable: {e})")
        lines.append("")
        lines.append("-- device memory (memwatch ledger) --")
        try:
            from .utils import memwatch
            mem = memwatch.WATCH.export()
            if mem["components"]:
                for e in sorted(mem["components"],
                                key=lambda e: (e["component"],
                                               sorted(e["labels"].items()))):
                    lbl = ",".join(f"{k}={v}" for k, v in
                                   sorted(e["labels"].items()))
                    tag = e["component"] + (f"{{{lbl}}}" if lbl else "")
                    host = " (host)" if e["host"] else ""
                    lines.append(f"{tag}: {e['bytes']:,}B{host}")
                lines.append(f"device total (model): "
                             f"{mem['device_total_bytes']:,}B")
            else:
                lines.append("(no components registered)")
        except Exception as e:  # noqa: BLE001 — statusz must render regardless
            lines.append(f"(memory ledger unavailable: {e})")
        lines.append("")
        n = int(self.query.get("n", 40)) if hasattr(self, "query") else 40
        lines.append(f"-- flight recorder (last {n}) --")
        lines.append(trace.RECORDER.render_text(n))
        return "\n".join(lines) + "\n"

    def _fleetz_text(self) -> str:
        """Merged fleet /metrics: this node's scrape + every peer's, summed
        per `utils/metrics.merge_prometheus` (counters + histogram buckets
        sum; gauges keep an `instance` label). Peers come from `?peers=`
        (comma-separated base URLs) or the node's `--peers` config;
        unreachable peers degrade to a comment line, never a 500 — a fleet
        view with one dead node is still a fleet view."""
        import urllib.request
        from .utils import metrics as metrics_mod
        from .utils import sketch
        sketch.MONITOR.publish()
        q = self.query.get("peers") if hasattr(self, "query") else None
        peers = ([p for p in q.split(",") if p] if q is not None
                 else list(self.peers))
        scrapes = [(self.node_info.get("node_id", "self"),
                    metrics_mod.prometheus_text())]
        comments = [f"# fleet: {1 + len(peers)} node(s): self + "
                    + (", ".join(peers) if peers else "(no peers)")]
        for peer in peers:
            url = peer.rstrip("/")
            if not url.startswith("http"):
                url = f"http://{url}"
            try:
                req = urllib.request.Request(
                    f"{url}/metrics", headers=trace.inject_headers())
                with urllib.request.urlopen(req, timeout=5.0) as r:
                    scrapes.append((peer, r.read().decode()))
            except Exception as e:  # noqa: BLE001 — degrade, don't 500
                comments.append(f"# fleet: peer {peer} unreachable: {e}")
                metrics_mod.observe("fleet.scrape_errors", 1)
        metrics_mod.observe("fleet.peers", float(len(peers)), "gauge")
        metrics_mod.observe("fleet.nodes_answering", float(len(scrapes)),
                            "gauge")
        merged = metrics_mod.merge_prometheus(scrapes)
        comments.extend(self._fleetz_freshness(merged))
        return "\n".join(comments) + "\n" + merged

    def _fleetz_freshness(self, merged: str) -> list:
        """"Who is stale" comment lines for /fleetz: per-instance
        `sync.freshness_ms` / head / applied version gauges parsed back OUT
        of the merged scrape (gauges keep their `instance` label through the
        merge, so no extra round-trips), plus THIS node's last hop
        breakdown from the lineage book."""
        out = []
        try:
            per: dict = {}
            for line in merged.splitlines():
                for metric, field in (("oetpu_sync_freshness_ms", "fresh"),
                                      ("oetpu_sync_head_version", "head"),
                                      ("oetpu_sync_applied_version",
                                       "applied")):
                    if not line.startswith(metric + "{"):
                        continue
                    m = re.search(r'instance="([^"]*)"', line)
                    inst = m.group(1) if m else "self"
                    try:
                        val = float(line.rsplit(None, 1)[-1])
                    except ValueError:
                        continue
                    per.setdefault(inst, {})[field] = val
            for inst in sorted(per):
                d = per[inst]
                parts = [f"# fleet freshness: {inst}:"]
                if "fresh" in d:
                    parts.append(f"freshness_ms={d['fresh']:.1f}")
                if "head" in d:
                    parts.append(f"head_version={int(d['head'])}")
                if "applied" in d:
                    parts.append(f"applied_version={int(d['applied'])}")
                out.append(" ".join(parts))
            from .sync import lineage
            last = lineage.BOOK.last()
            if last is not None and last.get("hops"):
                hops = " ".join(f"{h}={v:.1f}ms" for h, v in
                                sorted(last["hops"].items()))
                out.append(f"# fleet freshness: self last delta "
                           f"step={last['step']} hops: {hops}")
        except Exception as e:  # noqa: BLE001 — degrade, don't 500
            out.append(f"# fleet freshness: unavailable: {e}")
        return out

    def do_GET(self):  # noqa: N802 (http.server API)
        return self._traced("GET", self._handle_get)

    def _handle_get(self):
        kind, sign, action = self._route()
        try:
            if kind == "models":
                return self._json(200, self.manager.registry.show_models())
            if kind == "model" and action == "versions":
                # online-sync feed (sync/publisher.py): ETag = head commit
                # step; ?after=<step>&wait_s=<s> bounded long-poll -> 304
                # when nothing newer commits inside the window
                pub = self.publishers.get(sign)
                if pub is None:
                    return self._json(
                        404, {"error": f"model {sign!r} has no publisher"})
                after = self.query.get("after")
                after = (self._coerce(int, after, "after")
                         if after is not None else None)
                wait_s = self._coerce(float, self.query.get("wait_s", 0.0),
                                      "wait_s")
                feed, changed = pub.wait_versions(after, wait_s)
                etag = {"ETag": f'"{feed["head_step"]}"'}
                if not changed:
                    self.send_response(304)
                    self.send_header("ETag", etag["ETag"])
                    self.end_headers()
                    return None
                return self._json(200, feed, headers=etag)
            if kind == "model" and action == "syncstate":
                sub = self.subscribers.get(sign)
                if sub is None:
                    return self._json(
                        404, {"error": f"model {sign!r} has no subscriber"})
                return self._json(200, sub.status())
            if kind == "delta":
                pub = self.publishers.get(sign)
                if pub is None:
                    return self._json(
                        404, {"error": f"model {sign!r} has no publisher"})
                step, fname = action
                etag = {"ETag": f'"{step}"'}  # committed deltas are immutable
                if fname == "meta":
                    return self._json(200, pub.delta_meta(step), headers=etag)
                if fname == "dense":
                    return self._blob(pub.delta_dense(step), headers=etag)
                name = fname[len("table/"):]
                fmt = self.query.get("wire")
                if fmt is not None:
                    from .ops.wire import wire_format
                    fmt = self._coerce(wire_format, fmt, "wire")
                return self._blob(pub.delta_table(step, name, fmt),
                                  headers=etag)
            if kind == "model" and action in ("exportmeta", "rows", "dense"):
                # live-replica restore surface (reference
                # `EmbeddingRestoreOperator.cpp:19-106`: iterate a live
                # replica's rows through cursors): a peer pages these three
                # endpoints to rebuild a standalone export with no shared
                # filesystem — see `restore_from_peer`.
                model = self.manager.find_model(sign)
                if action == "exportmeta":
                    return self._json(200, model.export_manifest())
                if action == "dense":
                    return self._npz(model.export_dense())
                var = self.query.get("var")
                if var is None:
                    raise _BadRequest("rows: missing ?var=")
                if var not in model.variable_names:
                    return self._json(
                        404, {"error": f"model {sign!r} has no variable {var!r}"})
                start = self._coerce(int, self.query.get("start", 0), "start")
                count = self._coerce(int, self.query.get("count", 1 << 16),
                                     "count")
                from .export import _BadRange
                try:
                    return self._npz(model.export_rows(var, start, count))
                except _BadRange as e:
                    raise _BadRequest(str(e)) from e
            if kind == "model":
                entry = self.manager.registry.get(sign)
                if entry is None:
                    return self._json(404, {"error": f"unknown model {sign}"})
                return self._json(200, entry)
            if kind == "nodes":
                return self._json(200, {"nodes": [self.node_info]})
            if kind == "healthz":
                return self._json(200, {"status": "ok"})
            if kind == "metrics":
                from .utils import sketch
                from .utils.metrics import prometheus_text
                sketch.MONITOR.publish()  # fold top-K into skew.* gauges
                body = prometheus_text().encode()
                self.send_response(200)
                self.send_header("Content-Type", "text/plain; version=0.0.4")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)
                return None
            if kind == "fleetz":
                return self._text(self._fleetz_text())
            if kind == "statusz":
                return self._text(self._statusz_text())
            if kind == "tracez":
                n = self._coerce(int, self.query.get("n", 256), "n")
                return self._json(200, {
                    "spans": [s.as_dict() for s in trace.RECORDER.spans(n)],
                    "events": [e.as_dict()
                               for e in trace.RECORDER.events(n)]})
            if kind == "sloz":
                # evaluate on demand (the background thread is optional):
                # every scrape judges the freshest accumulator state
                from .utils import slo
                verdicts = slo.EVALUATOR.evaluate_now()
                if self.query.get("format") == "text":
                    return self._text(slo.EVALUATOR.render_text())
                return self._json(200, {"verdicts": verdicts,
                                        "exit_code":
                                            slo.EVALUATOR.exit_code()})
            if kind == "historz":
                # GET /historz?metric=<name>[&window=<s>][&<label>=<v>...] —
                # a metric's retained ring(s); without ?metric=, the series
                # catalogue (names only, cheap)
                from .utils import history
                metric = self.query.get("metric")
                if metric is None:
                    return self._json(200, {"metrics": history.HISTORY.names()})
                window = self.query.get("window")
                window_s = (self._coerce(float, window, "window")
                            if window is not None else None)
                labels = {k: v for k, v in self.query.items()
                          if k not in ("metric", "window")}
                return self._json(200, {
                    "metric": metric, "window_s": window_s,
                    "series": history.HISTORY.query(
                        metric, window_s=window_s, labels=labels or None)})
            if kind == "timelinez":
                # GET /timelinez[?n=] — this node's flight events/spans with
                # (wall, monotonic) pairs, its delta lineage book, and clock
                # info; `tools/fleet_timeline.py` scrapes N of these, solves
                # per-node skew Cristian-style off `wall_time`, and renders
                # one merged causally-ordered fleet timeline
                from .sync import lineage
                n = self._coerce(int, self.query.get("n", 512), "n")
                return self._json(200, {
                    "node": self.node_info.get("node_id", "self"),
                    "process": trace.PROCESS_ID,
                    "wall_time": time.time(),
                    "events": [e.as_dict()
                               for e in trace.RECORDER.events(n)],
                    "spans": [s.as_dict() for s in trace.RECORDER.spans(n)],
                    "lineage": lineage.BOOK.export()})
            return self._json(404, {"error": "not found"})
        except _BadRequest as e:
            return self._json(400, {"error": str(e)})
        except KeyError as e:
            return self._json(404, {"error": str(e)})
        except Exception as e:  # noqa: BLE001 - every handler error becomes a 500
            return self._json(500, {"error": str(e)})

    @staticmethod
    def _field(body: dict, *names):
        """Required request-body field: first present name wins; absence is the
        CALLER's error (400), never a 404 — 404 is reserved for unknown
        model/variable signs."""
        for n in names:
            if n in body:
                return body[n]
        raise _BadRequest(f"missing required field {names[0]!r}")

    @staticmethod
    def _coerce(fn, value, what: str):
        """Convert a request value, mapping conversion failures to 400 at the
        parse site — a ValueError/TypeError deep inside model code is a real
        server error and must stay a 500."""
        try:
            return fn(value)
        except (ValueError, TypeError) as e:
            raise _BadRequest(f"bad {what!r}: {e}") from e

    def do_POST(self):  # noqa: N802
        return self._traced("POST", self._handle_post)

    def _handle_post(self):
        kind, sign, action = self._route()
        try:
            body = self._body()
            if kind == "capsule":
                # POST /capsule {"reason": ..., ...attrs} — operator-requested
                # postmortem dump; 409 when capsules are not armed (no dir),
                # 429 when the per-reason rate limit suppressed the write
                from .utils import capsule
                if not capsule.enabled():
                    return self._json(409, {
                        "error": "capsules not configured "
                                 "(--capsule-dir / OETPU_CAPSULE_DIR)"})
                reason = str(body.pop("reason", "operator"))
                path = capsule.trigger(reason, **{
                    str(k): v for k, v in body.items()})
                # single exit: 200 with the path, or 429 when the per-reason
                # rate limit (or a write error) suppressed the dump
                return self._json(
                    200 if path else 429,
                    {"reason": reason, "path": path} if path
                    else {"error": "capsule suppressed (rate limit or "
                                   "write error)", "reason": reason})
            if kind == "models" or (kind == "model" and action is None):
                # POST /models {model_sign, model_uri, replica_num, shard_num}
                # (controller.proto CreateModelRequest fields)
                sign = sign or self._field(body, "model_sign")
                entry = self.manager.load_model(
                    sign, self._field(body, "model_uri", "uri"),
                    replica_num=self._coerce(int, body.get("replica_num", 1),
                                             "replica_num"),
                    shard_num=self._coerce(int, body.get("shard_num", 1),
                                           "shard_num"))
                return self._json(200, entry)
            if kind == "model" and action == "publish":
                # register this node as the sync publisher for `sign`:
                # POST /models/<sign>/publish {"persist_root": ..., "wire": ...}
                from .sync import SyncPublisher
                root = self._field(body, "persist_root", "root")
                if not os.path.isdir(root):
                    raise _BadRequest(f"persist_root {root!r} is not a "
                                      "directory")
                pub = SyncPublisher(root, wire=body.get("wire"))
                self.publishers[sign] = pub
                return self._json(200, {"model_sign": sign,
                                        **pub.versions()})
            if kind == "model" and action == "sync":
                # attach a live subscriber on this serving node:
                # POST /models/<sign>/sync {"feed": url, "interval_s": ...,
                #                           "wire": ..., "wait_s": ...}
                from .sync import SyncSubscriber
                feed = self._field(body, "feed")
                old = self.subscribers.pop(sign, None)
                if old is not None:
                    old.stop()
                sub = SyncSubscriber(
                    self.manager, sign, feed,
                    wire=body.get("wire"),
                    interval_s=self._coerce(
                        float, body.get("interval_s", 1.0), "interval_s"),
                    wait_s=self._coerce(
                        float, body.get("wait_s", 0.0), "wait_s"))
                self.subscribers[sign] = sub.start()
                return self._json(200, sub.status())
            if kind == "model" and action == "pull":
                model, variable = self.manager.find_model_variable(
                    sign, self._field(body, "variable"))
                ids = self._coerce(_pull_ids, self._field(body, "ids"),
                                   "ids")
                # heavy-hitter telemetry, off the hot path (bounded queue;
                # predict ids are recorded by the servables themselves)
                from .utils import sketch
                sketch.record_ids(variable, ids)
                rows = model.lookup(variable, ids)
                # content negotiation: `Accept: application/octet-stream`
                # streams the rows as npz — JSON-encoding a big pull is pure
                # overhead for programmatic clients (ServingClient binary=True)
                if "application/octet-stream" in self.headers.get("Accept", ""):
                    return self._npz({"weights": np.asarray(rows)})
                return self._json(200, {"weights": np.asarray(rows).tolist()})
            if kind == "model" and action == "predict":
                # per-request wall time -> labeled latency histogram
                # (oetpu_serving_predict_ms_bucket{model=...}) AND a span
                # under the request's http span — one measurement, two views
                with trace.span("serving", "predict",
                                labels={"model": sign}, model=sign):
                    model = self.manager.find_model(sign)
                    pooled = _pooled_features(model)
                    batch = {
                        "sparse": {k: self._coerce(
                            lambda v, _p=(k in pooled):
                                _ids_array(v, pooled=_p),
                            v, f"sparse.{k}")
                            for k, v in body.get("sparse", {}).items()},
                    }
                    if body.get("dense") is not None:
                        batch["dense"] = self._coerce(
                            lambda v: np.asarray(v, dtype=np.float32),
                            body["dense"], "dense")
                    from .export import RaggedBatchError
                    try:
                        if self.batcher is not None:
                            logits = self.batcher.predict(model, sign, batch)
                        else:
                            with trace.span("serving", "model_call"):
                                logits = model.predict(batch)
                    except KeyError as e:
                        # a feature the model needs is absent from the request
                        # body — the CALLER's error (400), not an unknown sign
                        raise _BadRequest(
                            f"predict request is missing sparse feature {e}"
                        ) from e
                    except RaggedBatchError as e:
                        raise _BadRequest(str(e)) from e
                    # close the delta's lineage chain on its FIRST predict
                    # at this version (idempotent, O(1), no-throw)
                    from .sync import lineage
                    lineage.note_serve(
                        sign, int(getattr(model, "step", 0) or 0))
                    return self._json(
                        200, {"logits": np.asarray(logits).tolist()})
            return self._json(404, {"error": "not found"})
        except _BadRequest as e:
            return self._json(400, {"error": str(e)})
        except json.JSONDecodeError as e:
            return self._json(400, {"error": f"malformed request body: {e}"})
        except KeyError as e:
            return self._json(404, {"error": str(e)})
        except Exception as e:  # noqa: BLE001
            return self._json(500, {"error": str(e)})

    def do_DELETE(self):  # noqa: N802
        return self._traced("DELETE", self._handle_delete)

    def _handle_delete(self):
        kind, sign, _ = self._route()
        try:
            if kind == "model":
                sub = self.subscribers.pop(sign, None)
                if sub is not None:
                    sub.stop()  # a deleted model must not keep syncing
                self.manager.registry.set_status(sign, "DELETING")
                self.manager.evict(sign)
                self.manager.registry.delete_model(sign)
                return self._json(200, {"deleted": sign})
            if kind == "node":
                # reference: controller can shut nodes down
                # (`ModelController.cpp:158-164`); here the node is this process
                # oelint: disable=thread-lifecycle -- shutdown() must run off
                # the request thread (it blocks until this very handler
                # returns); the thread self-terminates with the server
                threading.Thread(target=self.server.shutdown, daemon=True).start()
                return self._json(200, {"shutdown": sign})
            return self._json(404, {"error": "not found"})
        except KeyError as e:
            return self._json(404, {"error": str(e)})
        except Exception as e:  # noqa: BLE001
            return self._json(500, {"error": str(e)})


class ServingClient:
    """REST client with replica failover — the caller-side half of serving HA.

    The reference picks one replica per pull and retries on `NoReplica`
    (`pick_one_replica`, `EmbeddingPullOperator.cpp:50-58`,
    `c_api_test.h:117-121`); here the client walks its replica list starting
    from a rotating offset (spreads load) and fails over to the next node on
    connection errors. Server-side (HTTP) errors are NOT retried — a 400/404
    is the same answer everywhere, and a 500 on one replica is surfaced, not
    masked by silently asking another."""

    def __init__(self, nodes, timeout: float = 30.0):
        if isinstance(nodes, str):
            nodes = [nodes]
        if not nodes:
            raise ValueError("need at least one serving node URL")
        self.nodes = [n.rstrip("/") for n in nodes]
        self.timeout = timeout
        self._next = 0

    def _request(self, method: str, path: str, body=None, *,
                 binary: bool = False):
        import io
        import urllib.error
        import urllib.request
        start, last = self._next, None
        self._next = (self._next + 1) % len(self.nodes)
        for i in range(len(self.nodes)):
            node = self.nodes[(start + i) % len(self.nodes)]
            data = json.dumps(body).encode() if body is not None else None
            req = urllib.request.Request(f"{node}{path}", data=data,
                                         method=method,
                                         headers=trace.inject_headers())
            if data:
                req.add_header("Content-Type", "application/json")
            if binary:
                req.add_header("Accept", "application/octet-stream")
            try:
                with urllib.request.urlopen(req, timeout=self.timeout) as r:
                    raw = r.read()
                    if binary and "octet-stream" in r.headers.get(
                            "Content-Type", ""):
                        return dict(np.load(io.BytesIO(raw)))
                    return json.loads(raw)
            except urllib.error.HTTPError:
                raise  # a server ANSWERED; its answer stands (see class doc)
            except (urllib.error.URLError, ConnectionError, OSError) as e:
                last = e  # dead/unreachable replica: try the next
        raise ConnectionError(
            f"no live replica among {self.nodes}: {last}") from last

    @staticmethod
    def _jsonable_ids(v):
        """RAGGED id lists stay lists (np.asarray would raise on inhomogeneous
        shapes before any request is made) — the server pads them
        (`_ids_array`/`_pull_ids`); everything else normalizes through numpy."""
        from .data import is_ragged
        if is_ragged(v):
            return [[int(x) for x in row] for row in v]
        return np.asarray(v).tolist()

    def pull(self, model_sign: str, variable: str, ids, *,
             binary: bool = False) -> np.ndarray:
        """`binary=True` asks for the npz wire format (Accept negotiation) —
        no JSON float round-trip, the right mode for large/hot pulls."""
        out = self._request("POST", f"/models/{model_sign}/pull",
                            {"variable": variable,
                             "ids": self._jsonable_ids(ids)},
                            binary=binary)
        if binary:
            return out["weights"]
        return np.asarray(out["weights"], np.float32)

    def predict(self, model_sign: str, sparse: Dict[str, Any],
                dense=None) -> np.ndarray:
        body = {"sparse": {k: self._jsonable_ids(v)
                           for k, v in sparse.items()}}
        if dense is not None:
            body["dense"] = np.asarray(dense).tolist()
        out = self._request("POST", f"/models/{model_sign}/predict", body)
        return np.asarray(out["logits"], np.float32)

    def create_model(self, model_sign: str, uri: str, *, replica_num: int = 1,
                     shard_num: int = 1) -> dict:
        return self._request("POST", "/models",
                             {"model_sign": model_sign, "model_uri": uri,
                              "replica_num": replica_num,
                              "shard_num": shard_num})

    def show_models(self) -> dict:
        return self._request("GET", "/models")


class MicroBatcher:
    """Aggregate concurrent /predict requests into one padded device batch.

    The reference delegates serving-side batching to TF-Serving's batcher
    (SavedModel + `documents/en/serving.md`); this is the same role for the
    REST node: a request parks up to `window_ms` waiting for companions, then
    one worker runs the whole group as a single `model.predict` (which pads to
    a power-of-two bucket, so grouped requests also share compiled programs).
    Groups are keyed by (model, feature-key set, id rank) — only structurally
    identical requests merge. Failures propagate to every member of the group.
    """

    def __init__(self, manager: "ModelManager", window_ms: float = 2.0,
                 max_batch: int = 4096):
        self.manager = manager
        self.window_s = window_ms / 1e3
        self.max_batch = max_batch
        self._lock = threading.Lock()
        self._full = threading.Condition(self._lock)
        self._groups: Dict[tuple, list] = {}  # guarded-by: self._lock

    @staticmethod
    def _group_key(sign: str, batch: dict) -> tuple:
        """Only structurally identical requests merge: same feature set AND
        same trailing shapes per feature (np.concatenate needs them), same
        dense width."""
        sparse = batch["sparse"]
        dense = batch.get("dense")
        return (sign,
                tuple((k, np.asarray(v).shape[1:])
                      for k, v in sorted(sparse.items())),
                None if dense is None else np.asarray(dense).shape[1:])

    @staticmethod
    def _request_rows(batch: dict) -> int:
        """Leading-dim row count; an INTERNALLY ragged request fails alone at
        enqueue (never poisoning its groupmates), and an empty request is the
        caller's error (KeyError -> the handler's 400)."""
        from .export import RaggedBatchError
        if not batch["sparse"]:
            raise KeyError("predict request has no sparse features")
        ns = {k: int(np.asarray(v).shape[0])
              for k, v in batch["sparse"].items()}
        if batch.get("dense") is not None:
            ns["dense"] = int(np.asarray(batch["dense"]).shape[0])
        if len(set(ns.values())) != 1:
            raise RaggedBatchError(
                f"ragged serving batch: row counts {ns}")
        return next(iter(ns.values()))

    def predict(self, model, sign: str, batch: dict) -> np.ndarray:
        """Blocking: returns this request's logits slice. `model` is the
        handler's already-resolved servable (resolving again inside the
        window would turn a mid-window DELETE into the wrong error class)."""
        n = self._request_rows(batch)
        entry = {"batch": batch, "n": n, "done": threading.Event(),
                 "out": None, "err": None, "t0": time.monotonic()}
        key = self._group_key(sign, batch)
        with self._lock:
            group = self._groups.setdefault(key, [])
            group.append(entry)
            leader = len(group) == 1
            if not leader and sum(e["n"] for e in group) >= self.max_batch:
                self._full.notify_all()  # wake the leader early
        # oelint: disable=atomicity -- leadership is decided once at enqueue
        # (len==1 under the lock) and never contested: followers only wait,
        # and the pop under the re-taken lock is the leader's own key, so the
        # snapshot cannot go stale between the two critical sections
        if leader:
            # the first arrival owns the window + the device call; a full
            # group releases it before the window expires
            with trace.span("serving", "queue_wait", role="leader", rows=n):
                deadline = time.monotonic() + self.window_s
                with self._lock:
                    while (time.monotonic() < deadline
                           and sum(e["n"] for e in self._groups.get(key, ()))
                           < self.max_batch):
                        self._full.wait(timeout=max(
                            0.0, deadline - time.monotonic()))
                    group = self._groups.pop(key, [])
            with trace.span("serving", "batch_exec", requests=len(group),
                            rows=sum(e["n"] for e in group)):
                self._run(model, group)
        else:
            # a follower's wait covers enqueue -> its group's exec finishing
            # (it cannot observe the run start; the leader's spans split it)
            with trace.span("serving", "queue_wait", role="follower", rows=n):
                entry["done"].wait()
        entry["done"].wait()
        if entry["err"] is not None:
            raise entry["err"]
        return entry["out"]

    def _run(self, model, group: list) -> None:
        # chunk so one merged call never exceeds max_batch rows
        chunk, rows = [], 0
        for e in group:
            if chunk and rows + e["n"] > self.max_batch:
                self._run_chunk(model, chunk)
                chunk, rows = [], 0
            chunk.append(e)
            rows += e["n"]
        if chunk:
            self._run_chunk(model, chunk)

    # oelint: hot-path -- every merged predict runs through here; the single
    # np.asarray(model.predict(...)) below is the ONE device sync per batch
    def _run_chunk(self, model, group: list) -> None:
        from .utils import metrics
        # window tunability (the `window_ms` knob): how long requests parked
        # waiting for companions, and how full the merged batch came out —
        # published next to predict_batches/predict_requests so the trade
        # reads straight off /metrics instead of guesswork
        now = time.monotonic()
        for e in group:
            metrics.observe("serving.batch_wait_ms",
                            (now - e["t0"]) * 1e3, "avg")
        metrics.observe("serving.batch_fill_ratio",
                        min(1.0, sum(e["n"] for e in group) / self.max_batch),
                        "avg")
        try:
            batches = [e["batch"] for e in group]
            merged = {"sparse": {
                k: np.concatenate([np.asarray(b["sparse"][k])
                                   for b in batches])
                for k in batches[0]["sparse"]}}
            if batches[0].get("dense") is not None:
                merged["dense"] = np.concatenate(
                    [np.asarray(b["dense"]) for b in batches])
            with trace.span("serving", "model_call",
                            rows=sum(e["n"] for e in group)):
                logits = np.asarray(model.predict(merged))
            metrics.observe("serving.predict_batches", 1)
            metrics.observe("serving.predict_requests", len(group))
            off = 0
            for e in group:
                e["out"] = logits[off:off + e["n"]]
                off += e["n"]
        except Exception as err:  # noqa: BLE001 — delivered to every waiter
            for e in group:
                e["err"] = err
        finally:
            for e in group:
                e["done"].set()


def restore_from_peer(peer: str, model_sign: str, dest: str, *,
                      page: int = 1 << 16, timeout: float = 60.0) -> str:
    """Rebuild a model's standalone export from a LIVE serving peer over REST.

    The reference replaces a dead serving node by iterating another replica's
    shard via (iterator_id, offset) cursors and shipping batched
    indices+weights (`server/EmbeddingRestoreOperator.cpp:19-106`,
    `entry/server.cc:52-55` `--restore`). Here the new node pages the peer's
    `:exportmeta` / `:rows` / `:dense` endpoints and writes a standard
    standalone export under `dest` — no shared filesystem required. Register
    `dest` with the local node (POST /models) to finish the restore.

    Crash safety: everything pages into `dest + ".tmp-<pid>"` and renames
    into place only after the LAST byte (meta/config included) is on disk —
    a mid-page peer death, timeout, or local crash can never leave a
    half-written export at `dest` for a later `ModelManager.create_model`
    to happily load. A pre-existing `dest` (e.g. a prior complete restore)
    is replaced only at that final swap.

    Returns `dest`. Raises on a peer error or a non-NORMAL model.
    """
    import io
    import shutil
    import urllib.request
    from urllib.parse import quote

    def get(path: str) -> bytes:
        req = urllib.request.Request(f"{peer}{path}",
                                     headers=trace.inject_headers())
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.read()

    entry = json.loads(get(f"/models/{model_sign}"))
    if entry.get("status") != "NORMAL":
        raise RuntimeError(
            f"peer model {model_sign!r} is {entry.get('status')!r}, "
            "not restorable")
    manifest = json.loads(get(f"/models/{model_sign}:exportmeta"))

    tmp = dest.rstrip("/\\") + f".tmp-{os.getpid()}"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    try:
        _page_restore(get, manifest, model_sign, tmp, peer, page,
                      final_uri=dest)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)  # never leave partial pages
        raise
    if os.path.exists(dest):
        shutil.rmtree(dest)  # replaced only once tmp is COMPLETE
    os.replace(tmp, dest)
    return dest


def _page_restore(get, manifest, model_sign: str, dest: str, peer: str,
                  page: int, final_uri: str) -> None:
    """Page the peer's rows/dense/meta into `dest` (restore_from_peer's
    staging dir — the caller owns atomic-rename/cleanup); the written meta
    records `final_uri`, where the export will land after the rename."""
    import io
    from urllib.parse import quote

    os.makedirs(dest, exist_ok=True)
    for v in manifest["variables"]:
        vdir = os.path.join(dest, f"variable_{v['variable_id']}")
        os.makedirs(vdir, exist_ok=True)
        chunks: Dict[str, list] = {"weights": [], "ids": []}
        for start in range(0, max(v["rows"], 1), page):
            if start >= v["rows"]:
                break  # zero-row table: write empty payloads below
            data = np.load(io.BytesIO(get(
                f"/models/{model_sign}:rows"
                f"?var={quote(v['storage_name'], safe='')}"
                f"&start={start}&count={page}")))
            chunks["weights"].append(data["weights"])
            if "ids" in data:
                chunks["ids"].append(data["ids"])
        w = (np.concatenate(chunks["weights"]) if chunks["weights"]
             else np.zeros((0, v["dim"]), np.float32))
        if w.shape[0] != v["rows"]:
            raise RuntimeError(
                f"peer returned {w.shape[0]} rows for {v['storage_name']!r}, "
                f"manifest says {v['rows']} (model changed mid-restore?)")
        np.save(os.path.join(vdir, "weights.npy"), w)
        if v["kind"] == "hash":
            ids = (np.concatenate(chunks["ids"]) if chunks["ids"]
                   else np.zeros((0,), np.int64))
            np.save(os.path.join(vdir, "ids.npy"), ids)

    dense = np.load(io.BytesIO(get(f"/models/{model_sign}:dense")))
    np.savez(os.path.join(dest, "dense_params.npz"),
             **{k: dense[k] for k in dense.files})

    meta = dict(manifest["meta"])
    meta["uri"] = final_uri
    meta["num_shards"] = 1  # the restored artifact is a standalone export
    # keep the written meta consistent with the written files: the peer's meta
    # may describe a sharded checkpoint (dense_manifest incl. __embeddings__/
    # entries that export_dense filters out, no `extra` block)
    meta["dense_manifest"] = {
        k: {"shape": list(dense[k].shape), "dtype": str(dense[k].dtype)}
        for k in dense.files}
    meta["extra"] = {"standalone": True,
                     "restored_from": f"{peer}/models/{model_sign}"}
    from .checkpoint import MODEL_META_FILE
    with open(os.path.join(dest, MODEL_META_FILE), "w") as f:
        json.dump(meta, f, indent=2, sort_keys=True)
    if manifest.get("model_config") is not None:
        from .export import MODEL_CONFIG_FILE
        with open(os.path.join(dest, MODEL_CONFIG_FILE), "w") as f:
            json.dump(manifest["model_config"], f, indent=2, sort_keys=True)


def make_server(registry_root: str, host: str = "127.0.0.1", port: int = 0, *,
                batch_window_ms: float = 0.0, max_batch: int = 4096,
                publish: Optional[Dict[str, str]] = None,
                publish_wire: Optional[str] = None,
                peers: Optional[list] = None
                ) -> ThreadingHTTPServer:
    """Build (not start) the serving HTTP server; port 0 picks a free port.
    `batch_window_ms > 0` turns on predict micro-batching (`MicroBatcher`).
    `publish` ({model_sign: persist_root}) registers online-sync publishers
    (the trainer-side half of `sync/`; more can be added at runtime via
    POST /models/<sign>/publish, and subscribers attach via
    POST /models/<sign>/sync). `peers` (base URLs of other fleet nodes)
    seeds the `GET /fleetz` merged-metrics scrape set (overridable per
    request with `?peers=`)."""
    registry = ModelRegistry(registry_root)
    manager = ModelManager(registry)

    class Handler(ServingHandler):
        pass

    Handler.manager = manager
    Handler.batcher = (MicroBatcher(manager, window_ms=batch_window_ms,
                                    max_batch=max_batch)
                       if batch_window_ms > 0 else None)
    Handler.publishers = {}
    Handler.subscribers = {}
    Handler.peers = list(peers or [])
    if publish:
        from .sync import SyncPublisher
        for sign, root in publish.items():
            Handler.publishers[sign] = SyncPublisher(root, wire=publish_wire)
    Handler.node_info = {"node_id": f"{os.uname().nodename}:{os.getpid()}",
                         "registry": registry_root,
                         "batch_window_ms": batch_window_ms,
                         "publishes": sorted(Handler.publishers),
                         "peers": Handler.peers}
    httpd = ThreadingHTTPServer((host, port), Handler)
    httpd.manager = manager
    httpd.publishers = Handler.publishers
    httpd.subscribers = Handler.subscribers
    return httpd


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="openembedding_tpu serving node (REST admin + inference)")
    ap.add_argument("--registry", required=True, help="registry root directory")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8501)
    ap.add_argument("--batch-window-ms", type=float, default=0.0,
                    help="micro-batch concurrent /predict requests inside this "
                         "window (0 = off; the reference's TF-Serving batcher "
                         "role)")
    ap.add_argument("--max-batch", type=int, default=4096,
                    help="largest merged predict batch (rows)")
    ap.add_argument("--publish", action="append", default=[],
                    metavar="SIGN=PERSIST_ROOT",
                    help="serve this persist root's committed delta chain as "
                         "the online-sync feed for SIGN (repeatable)")
    ap.add_argument("--sync-from", action="append", default=[],
                    metavar="SIGN=FEED_URL",
                    help="keep the loaded model SIGN fresh against a "
                         "publisher node's feed (repeatable; the model must "
                         "be loaded on this node)")
    ap.add_argument("--sync-interval", type=float, default=1.0,
                    help="subscriber poll interval, seconds")
    ap.add_argument("--sync-wire", default=None,
                    help="row encoding on the sync wire "
                         "(fp32|bf16|int8; default fp32)")
    ap.add_argument("--peers", action="append", default=[], metavar="URL",
                    help="other fleet nodes' base URLs (repeatable, or "
                         "comma-separated): GET /fleetz on this node merges "
                         "their /metrics with its own (counters + histogram "
                         "buckets sum, gauges keep an instance label)")
    ap.add_argument("--flight-recorder", type=int, default=0, metavar="N",
                    help="resize the span/event flight recorder ring buffer "
                         "(0 keeps the default; tail shows on GET /statusz, "
                         "full contents on GET /tracez)")
    ap.add_argument("--trace-dump", default=None, metavar="PATH",
                    help="on shutdown, write the flight recorder as "
                         "Chrome-trace JSON to PATH (chrome://tracing / "
                         "Perfetto; summarize with tools/trace_report.py)")
    ap.add_argument("--slo-specs", default=None, metavar="PATH",
                    help="JSON list of SLO specs (utils/slo.py; default: the "
                         "built-in predict-p99 / sync-freshness / numerics "
                         "set). Verdicts on GET /sloz and the /statusz panel")
    ap.add_argument("--slo-interval", type=float, default=0.0,
                    help="also evaluate SLOs on a background thread every S "
                         "seconds (0 = only on /sloz//statusz scrapes) — "
                         "breaches land in the flight recorder even when "
                         "nobody is scraping")
    ap.add_argument("--capsule-dir", default=None, metavar="DIR",
                    help="arm postmortem capsules: SLO breaches, WeaveLeaks "
                         "and POST /capsule write capsule-*.json.gz bundles "
                         "(flight tail + history rings + memory ledger) "
                         "here; render with tools/capsule_report.py")
    args = ap.parse_args(argv)
    from .utils import compile_cache
    compile_cache.enable()  # a restarted node reloads its gather/predict programs
    if args.flight_recorder > 0:
        trace.configure(args.flight_recorder)
    if args.capsule_dir:
        from .utils import capsule
        capsule.configure(args.capsule_dir)
        capsule.register_context(
            "serving", lambda: {"argv": list(argv) if argv else None,
                                "registry": args.registry,
                                "host": args.host, "port": args.port})
    from .utils import slo
    if args.slo_specs:
        slo.configure(slo.load_specs(args.slo_specs))
    slo_eval = None
    if args.slo_interval > 0:
        slo.EVALUATOR.interval_s = args.slo_interval
        slo_eval = slo.EVALUATOR.start()

    def kv(pairs, what):
        out = {}
        for p in pairs:
            if "=" not in p:
                ap.error(f"--{what} expects SIGN=VALUE, got {p!r}")
            k, v = p.split("=", 1)
            out[k] = v
        return out

    httpd = make_server(args.registry, args.host, args.port,
                        batch_window_ms=args.batch_window_ms,
                        max_batch=args.max_batch,
                        publish=kv(args.publish, "publish"),
                        publish_wire=args.sync_wire,
                        peers=[p for arg in args.peers
                               for p in arg.split(",") if p])
    from .sync import SyncSubscriber
    for sign, feed in kv(args.sync_from, "sync-from").items():
        httpd.subscribers[sign] = SyncSubscriber(
            httpd.manager, sign, feed, wire=args.sync_wire,
            interval_s=args.sync_interval).start()
    print(f"serving on http://{args.host}:{httpd.server_address[1]} "
          f"(registry: {args.registry})")
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        for sub in httpd.subscribers.values():
            sub.stop()
        if slo_eval is not None:
            slo_eval.stop()
        if args.trace_dump:
            print(f"trace dump: {trace.dump_chrome(args.trace_dump)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
