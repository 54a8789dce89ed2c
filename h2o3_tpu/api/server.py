"""REST server: versioned JSON routes over the runtime — water/api analog.

Reference: ``water/api/RequestServer.java:56,75-80`` (~150 routes, versioned
schemas under ``water/api/schemas3``), served by Jetty adapters
(h2o-webserver-iface).  Clients (h2o-py/h2o-r/Flow) drive everything through
these routes.

TPU-native redesign: a stdlib ThreadingHTTPServer (no Jetty analog needed —
the control plane is a single coordinator process; the data plane never
touches HTTP).  Routes keep the reference's shapes/paths so an h2o-py-style
client maps 1:1: /3/Cloud, /3/Jobs, /3/Frames, /3/Parse, /3/ModelBuilders/
{algo}, /3/Models, /3/Predictions/models/{m}/frames/{f}, /3/DKV.
"""

from __future__ import annotations

import json
import os
import re
import tempfile
import threading
import time
import traceback
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, Optional, Tuple
from urllib.parse import parse_qs, urlparse

import numpy as np

ALGOS = ("glm", "gbm", "drf", "xgboost", "deeplearning", "kmeans", "pca",
         "svd", "naivebayes", "isolationforest", "extendedisolationforest",
         "isotonicregression", "quantile", "stackedensemble", "adaboost",
         "targetencoder", "glrm", "coxph", "word2vec", "rulefit",
         "aggregator", "gam", "upliftdrf", "dt", "psvm", "anovaglm",
         "modelselection", "infogram")


def _builder(algo: str):
    from .. import models as M
    return {
        "glm": M.GLM, "gbm": M.GBM, "drf": M.DRF, "xgboost": M.XGBoost,
        "deeplearning": M.DeepLearning, "kmeans": M.KMeans, "pca": M.PCA,
        "svd": M.SVD, "naivebayes": M.NaiveBayes,
        "isolationforest": M.IsolationForest,
        "extendedisolationforest": M.ExtendedIsolationForest,
        "isotonicregression": M.IsotonicRegression,
        "quantile": M.Quantile, "stackedensemble": M.StackedEnsemble,
        "adaboost": M.AdaBoost, "targetencoder": M.TargetEncoder,
        "glrm": M.GLRM, "coxph": M.CoxPH, "word2vec": M.Word2Vec,
        "rulefit": M.RuleFit, "aggregator": M.Aggregator, "gam": M.GAM,
        "upliftdrf": M.UpliftDRF, "dt": M.DecisionTree,
        "psvm": M.PSVM, "anovaglm": M.ANOVAGLM,
        "modelselection": M.ModelSelection, "infogram": M.Infogram,
    }[algo]


def _frame_schema(key: str, fr) -> dict:
    return {
        "frame_id": {"name": key},
        "rows": fr.nrows, "columns": [
            {"label": n, "type": v.type,
             "domain": v.domain,
             "missing_count": int(v.nmissing()) if v.data is not None else 0}
            for n, v in zip(fr.names, fr.vecs)],
    }


def _model_schema(key: str, m) -> dict:
    def metr(x):
        if x is None:
            return None
        if isinstance(x, dict):
            return x
        d = x.describe() if hasattr(x, "describe") else {}
        return {k: v for k, v in d.items()
                if isinstance(v, (int, float, str, bool))}
    return {
        "model_id": {"name": key},
        "algo": m.algo,
        "response_column": m.params.response_column,
        "training_metrics": metr(m.training_metrics),
        "validation_metrics": metr(m.validation_metrics),
        "cross_validation_metrics": metr(m.cross_validation_metrics),
        "output": {k: v for k, v in m.output.items()
                   if isinstance(v, (int, float, str, bool))},
    }


class _Server(ThreadingHTTPServer):
    """HTTP server with optional per-connection TLS (deferred handshake)
    and in-flight handler tracking so shutdown can drain gracefully."""

    ssl_context = None
    daemon_threads = True
    block_on_close = False        # drain() bounds the wait instead

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._inflight: set = set()
        self._inflight_lock = threading.Lock()

    def get_request(self):
        sock, addr = super().get_request()
        if self.ssl_context is not None:
            sock = self.ssl_context.wrap_socket(
                sock, server_side=True, do_handshake_on_connect=False)
        return sock, addr

    def process_request_thread(self, request, client_address):
        t = threading.current_thread()
        with self._inflight_lock:
            self._inflight.add(t)
        try:
            super().process_request_thread(request, client_address)
        finally:
            with self._inflight_lock:
                self._inflight.discard(t)

    def drain(self, timeout: float) -> int:
        """Wait (bounded) for in-flight request handlers; returns how many
        were still running when the deadline hit."""
        deadline = time.time() + timeout
        while True:
            with self._inflight_lock:
                live = [t for t in self._inflight
                        if t.is_alive() and t is not threading.current_thread()]
            if not live or time.time() >= deadline:
                return len(live)
            live[0].join(timeout=min(0.1, max(deadline - time.time(), 0.01)))


class _Handler(BaseHTTPRequestHandler):
    timeout = 120                               # bounds a stalled peer
    routes_get: Dict[str, Callable] = {}
    routes_post: Dict[str, Callable] = {}
    routes_delete: Dict[str, Callable] = {}

    def log_message(self, fmt, *args):          # quiet
        pass

    def _authorized(self) -> bool:
        """Pluggable authn (api/auth.py SPI): a valid form-login session
        cookie OR HTTP Basic checked against the configured Authenticator.
        Reference surface: h2o-security / h2o-jaas-pam login services."""
        authn = getattr(self.server, "authenticator", None)
        if authn is None:
            return True
        from . import auth as _auth
        sessions = self.server.sessions
        token = _auth.parse_cookie(self.headers.get("Cookie", ""),
                                   "h2o3-session")
        if token and sessions.user_for(token):
            return True
        creds = _auth.parse_basic(self.headers.get("Authorization", ""))
        return bool(creds) and authn.check(*creds)

    def _do_login(self, params: dict):
        """POST /3/Login (form fields username/password) -> session cookie.

        The form-login flow (h2o-security LoginHandler analog): Flow and
        browser clients authenticate once and carry the cookie."""
        from . import auth as _auth
        authn = self.server.authenticator
        user = str(params.get("username", ""))
        password = str(params.get("password", ""))
        if authn is None or authn.check(user, password):
            body = json.dumps({"login": "ok", "username": user}).encode()
            self.send_response(200)
            if authn is not None:
                token = self.server.sessions.create(user)
                self.send_header(
                    "Set-Cookie",
                    f"h2o3-session={token}; HttpOnly; Path=/; SameSite=Lax")
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self._reply(401, {"error": "invalid credentials"})

    def _do_logout(self):
        from . import auth as _auth
        token = _auth.parse_cookie(self.headers.get("Cookie", ""),
                                   "h2o3-session")
        if token:
            self.server.sessions.destroy(token)
        self._reply(200, {"logout": "ok"})

    def _reply(self, code: int, payload: dict):
        body = json.dumps(payload, default=_json_default).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_html(self, html: str):
        body = html.encode()
        self.send_response(200)
        self.send_header("Content-Type", "text/html; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _reply_text(self, text: str):
        # Prometheus text exposition (the only str-returning route)
        body = text.encode()
        self.send_response(200)
        self.send_header("Content-Type",
                         "text/plain; version=0.0.4; charset=utf-8")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _deny(self):
        self.send_response(401)
        self.send_header("WWW-Authenticate", 'Basic realm="h2o3_tpu"')
        self.end_headers()

    def _dispatch(self, table):
        if not self._authorized():
            return self._deny()
        parsed = urlparse(self.path)
        params = {k: v[0] for k, v in parse_qs(parsed.query).items()}
        length = int(self.headers.get("Content-Length") or 0)
        if length:
            raw = self.rfile.read(length)
            try:
                params.update(json.loads(raw))
            except Exception:
                params.update({k: v[0] for k, v
                               in parse_qs(raw.decode()).items()})
        for pattern, fn in table.items():
            m = re.fullmatch(pattern, parsed.path)
            if m:
                try:
                    out = fn(self.server.api, *m.groups(), **params)
                    if isinstance(out, bytes):       # artifact downloads
                        return self._reply_bytes(out)
                    if isinstance(out, str):         # /metrics exposition
                        return self._reply_text(out)
                    return self._reply(200, out)
                except KeyError as e:
                    return self._reply(404, {"error": str(e)})
                except Exception as e:      # noqa: BLE001
                    from ..serving.batcher import DeadlineExceeded
                    if isinstance(e, DeadlineExceeded):
                        # shed, not failed: retryable service pressure
                        return self._reply(503, {"error": str(e)})
                    return self._reply(400, {
                        "error": repr(e),
                        "stacktrace": traceback.format_exc().splitlines()})
        self._reply(404, {"error": f"no route {parsed.path}"})

    def _reply_bytes(self, data: bytes):
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_GET(self):
        path = urlparse(self.path).path
        if path in ("/", "/flow", "/flow/index.html"):
            if not self._authorized():
                return self._deny()
            from .flow import FLOW_HTML
            return self._reply_html(FLOW_HTML)
        self._dispatch(self.routes_get)

    def do_POST(self):
        path = urlparse(self.path).path
        if path == "/3/Login":
            length = int(self.headers.get("Content-Length") or 0)
            raw = self.rfile.read(length) if length else b""
            try:
                params = json.loads(raw)
                if not isinstance(params, dict):
                    raise ValueError("login body must be an object")
            except Exception:           # noqa: BLE001 — form-encoded body
                try:
                    params = {k: v[0] for k, v in
                              parse_qs(raw.decode()).items()}
                except Exception:       # noqa: BLE001 — binary garbage
                    return self._reply(400, {"error": "malformed login "
                                                      "body"})
            return self._do_login(params)
        if path == "/3/Logout":
            return self._do_logout()
        if path in ("/3/Models.upload.bin", "/3/PostFile"):
            # raw binary body (artifact / file upload), not JSON
            if not self._authorized():
                return self._deny()
            length = int(self.headers.get("Content-Length") or 0)
            raw = self.rfile.read(length)
            try:
                if path == "/3/PostFile":
                    q = {k: v[0] for k, v in
                         parse_qs(urlparse(self.path).query).items()}
                    return self._reply(200, self.server.api.post_file(
                        raw, filename=q.get("filename", "upload")))
                return self._reply(200, self.server.api.model_upload(raw))
            except Exception as e:          # noqa: BLE001
                return self._reply(400, {"error": repr(e)})
        self._dispatch(self.routes_post)

    def do_DELETE(self):
        self._dispatch(self.routes_delete)


def _json_default(o):
    if isinstance(o, (np.integer,)):
        return int(o)
    if isinstance(o, (np.floating,)):
        v = float(o)
        return v if np.isfinite(v) else None
    if isinstance(o, np.ndarray):
        return o.tolist()
    return str(o)


class Api:
    """Route implementations bound to the in-process runtime."""

    def __init__(self):
        self._lock = threading.Lock()
        self.jobs: Dict[str, dict] = {}

    # ---------------------------------------------------------------- cloud
    def cloud(self) -> dict:
        from ..runtime.cluster import cluster
        from ..runtime import heartbeat
        c = cluster().describe()
        members = heartbeat.members()
        healthy = all(m["status"] == "alive" for m in members.values())
        return {"version": "h2o3_tpu", "cloud_healthy": healthy,
                "cloud_size": c["process_count"], "members": members, **c}

    # ---------------------------------------------------------------- frames
    def frames(self) -> dict:
        from ..runtime import dkv
        from ..frame.frame import Frame
        out = []
        for k in dkv.keys():
            v = dkv.get(k)
            if isinstance(v, Frame):
                out.append(_frame_schema(k, v))
        return {"frames": out}

    def frame(self, key: str) -> dict:
        from ..runtime import dkv
        fr = dkv.get(key)
        if fr is None:
            raise KeyError(f"no frame {key!r}")
        return {"frames": [_frame_schema(key, fr)]}

    def parse(self, source_frames=None, destination_frame=None, path=None,
              col_types=None, **kw) -> dict:
        import os
        import tempfile
        from .. import import_file
        src = path or source_frames
        if isinstance(col_types, str):
            col_types = json.loads(col_types)
        fr = import_file(src, destination_frame=destination_frame,
                         **({"col_types": col_types} if col_types else {}))
        # a PostFile spool is single-use: delete once parsed so repeated
        # uploads cannot leak disk on a long-lived coordinator
        spool = os.path.join(tempfile.gettempdir(), "h2o3_uploads")
        for p in ([src] if isinstance(src, str) else list(src or [])):
            if isinstance(p, str) and os.path.dirname(p) == spool:
                try:
                    os.unlink(p)
                except OSError:
                    pass
        return {"job": {"status": "DONE"},
                "destination_frame": {"name": fr.key}}

    # ---------------------------------------------------------------- models
    @staticmethod
    def _coerce(params: dict) -> dict:
        """Coerce numeric/JSON strings (query-string transport)."""
        clean = {}
        for k, v in params.items():
            if isinstance(v, str):
                try:
                    v = json.loads(v)
                except Exception:
                    pass
            clean[k] = v
        return clean

    def _frame_pair(self, params: dict):
        from ..runtime import dkv
        training = params.pop("training_frame")
        valid_key = params.pop("validation_frame", None)
        frame = dkv.get(training)
        if frame is None:
            raise KeyError(f"no frame {training!r}")
        valid = dkv.get(valid_key) if valid_key else None
        return frame, valid

    def train(self, algo: str, **params) -> dict:
        algo = algo.lower()
        if algo not in ALGOS:
            raise KeyError(f"unknown algo {algo!r}")
        frame, valid = self._frame_pair(params)
        clean = self._coerce(params)
        model = _builder(algo)(**clean).train(frame, valid)
        return {"job": {"status": "DONE",
                        "dest": {"name": model.key}},
                "model": _model_schema(model.key, model)}

    def models(self) -> dict:
        from ..runtime import dkv
        from ..models.base import Model
        out = []
        for k in dkv.keys():
            v = dkv.get(k)
            if isinstance(v, Model):
                out.append(_model_schema(k, v))
        return {"models": out}

    def model(self, key: str) -> dict:
        from ..runtime import dkv
        m = dkv.get(key)
        if m is None:
            raise KeyError(f"no model {key!r}")
        return {"models": [_model_schema(key, m)]}

    def predict(self, model_key: str, frame_key: str, **kw) -> dict:
        from ..runtime import dkv
        m = dkv.get(model_key)
        fr = dkv.get(frame_key)
        if m is None or fr is None:
            raise KeyError(f"missing {model_key!r} or {frame_key!r}")
        pred = m.predict(fr)
        dest = kw.get("predictions_frame") or f"{model_key}_preds"
        pred.key = dest
        from ..runtime import dkv as _dkv
        _dkv.put(dest, pred)
        return {"predictions_frame": {"name": dest},
                "frames": [_frame_schema(dest, pred)]}

    # ------------------------------------------------------- online serving
    def predict_realtime(self, model_key: str, **kw) -> dict:
        """POST /3/Predictions/realtime/{model} — online row scoring
        through the packed-ensemble micro-batcher (h2o3_tpu/serving/).

        Body: ``{"row": {...}}`` or ``{"rows": [{...}, ...]}``; optional
        ``score_mode`` ("packed" | "ref" | "check") for parity drills.
        """
        from .. import serving
        entry = serving.ensure_published(model_key)
        rows = kw.get("rows")
        if rows is None and "row" in kw:
            rows = [kw["row"]]
        if not rows or not isinstance(rows, list):
            raise ValueError("realtime predict needs 'row' (object) or "
                             "'rows' (list of objects)")
        out = entry.predict_rows(rows, score_mode=kw.get("score_mode"))
        preds = []
        for i in range(len(rows)):
            p = {"predict": out["predict"][i]}
            if "probabilities" in out:
                p["probabilities"] = out["probabilities"][i]
            preds.append(p)
        return {"model_id": {"name": model_key}, "predictions": preds}

    def publish_realtime(self, model_key: str, **kw) -> dict:
        """POST /3/Predictions/realtime/{model}/warmup — pack, publish
        and AOT-warm the serving executable at model-publish time so the
        first live request never pays a compile."""
        from .. import serving
        entry = serving.publish(model_key)
        pk = entry.scorer.packed
        return {"model_id": {"name": model_key}, "published": True,
                "warmup_seconds": entry.warmup_s,
                "n_nodes": pk.n_nodes, "packed_bytes": pk.nbytes(),
                "max_batch": entry.batcher.max_batch}

    # ----------------------------------------------------------------- grids
    def grid_train(self, algo: str, **params) -> dict:
        """POST /99/Grid/{algo} — hyperparameter search
        (water/api/GridSearchHandler / hex/grid/GridSearch.java)."""
        from ..runtime import dkv
        from ..models.grid import GridSearch
        algo = algo.lower()
        if algo not in ALGOS:
            raise KeyError(f"unknown algo {algo!r}")
        frame, valid = self._frame_pair(params)
        clean = self._coerce(params)
        hyper = clean.pop("hyper_parameters", None) or {}
        criteria = clean.pop("search_criteria", None)
        sort_metric = clean.pop("sort_metric", None)
        grid = GridSearch(_builder(algo), hyper,
                          search_criteria=criteria, **clean).train(
            frame, valid, sort_metric=sort_metric)
        # Grid.__init__ registered itself in the DKV
        return self._grid_schema(grid)

    @staticmethod
    def _grid_schema(grid) -> dict:
        return {"grid_id": {"name": grid.key},
                "hyper_names": grid.hyper_names,
                "model_ids": [{"name": m.key} for m in grid.models],
                "sort_metric": grid.sort_metric,
                "summary_table": grid.sorted_metric_table(),
                # GridSchemaV99 failure_details analog: one entry per
                # member that failed to build (combo params + error)
                "failed_entries": grid.failed_entries}

    def grids(self) -> dict:
        from ..runtime import dkv
        from ..models.grid import Grid
        out = []
        for k in dkv.keys("grid"):
            v = dkv.get(k)
            if isinstance(v, Grid):
                out.append({"name": k})
        return {"grids": out}

    def grid(self, key: str) -> dict:
        from ..runtime import dkv
        g = dkv.get(key)
        if g is None:
            raise KeyError(f"no grid {key!r}")
        return self._grid_schema(g)

    # ---------------------------------------------------------------- automl
    def automl_build(self, **params) -> dict:
        """POST /99/AutoMLBuilder — run AutoML
        (ai/h2o/automl/AutoML.java:49 via AutoMLBuilderHandler)."""
        from ..runtime import dkv
        from ..automl import AutoML
        frame, valid = self._frame_pair(params)
        clean = self._coerce(params)
        project = clean.pop("project_name", None) or dkv.make_key("automl")
        aml = AutoML(**clean)
        leader = aml.train(frame, valid)
        dkv.put(f"automl_{project}", aml)
        return {"project_name": project,
                "leader": {"name": leader.key},
                "leaderboard_table": aml.leaderboard.as_table()
                if aml.leaderboard else []}

    def leaderboard(self, project: str) -> dict:
        """GET /99/Leaderboards/{project} (LeaderboardsHandler)."""
        from ..runtime import dkv
        aml = dkv.get(f"automl_{project}")
        if aml is None or aml.leaderboard is None:
            raise KeyError(f"no automl project {project!r}")
        lb = aml.leaderboard
        return {"project_name": project,
                "sort_metric": lb.sort_metric,
                "leaderboard_table": lb.as_table()}

    # ------------------------------------------------- model save / download
    def model_save(self, key: str, dir: str, **kw) -> dict:
        """POST /99/Models.bin/{model} — server-side save (h2o.save_model)."""
        from ..runtime import dkv
        m = dkv.get(key)
        if m is None:
            raise KeyError(f"no model {key!r}")
        path = f"{dir.rstrip('/')}/{key}.bin" if not dir.endswith(".bin") \
            else dir
        return {"path": m.save(path)}

    def model_fetch_bin(self, key: str) -> bytes:
        """GET /3/Models.fetch.bin/{model} — binary artifact download."""
        import os
        import tempfile
        from ..runtime import dkv
        m = dkv.get(key)
        if m is None:
            raise KeyError(f"no model {key!r}")
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "model.bin")
            m.save(p)
            with open(p, "rb") as f:
                return f.read()

    def model_fetch_mojo(self, key: str) -> bytes:
        """GET /3/Models/{model}/mojo — portable scoring artifact
        (ModelsHandler.fetchMojo analog)."""
        import os
        import tempfile
        from ..runtime import dkv
        from ..export.mojo import export_mojo
        m = dkv.get(key)
        if m is None:
            raise KeyError(f"no model {key!r}")
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "model.zip")
            export_mojo(m, p)
            with open(p, "rb") as f:
                return f.read()

    def post_file(self, raw: bytes, filename: str = "upload") -> dict:
        """POST /3/PostFile — push raw file bytes to the cluster
        (water/api/PostFileHandler analog); returns the server-side path
        to feed /3/Parse."""
        import os
        import tempfile
        base = os.path.join(tempfile.gettempdir(), "h2o3_uploads")
        os.makedirs(base, exist_ok=True)
        safe = os.path.basename(filename) or "upload"
        fd, path = tempfile.mkstemp(suffix="_" + safe, dir=base)
        with os.fdopen(fd, "wb") as f:
            f.write(raw)
        return {"destination_key": path, "total_bytes": len(raw)}

    def model_upload(self, raw: bytes, **kw) -> dict:
        """POST /3/Models.upload.bin — install a client-side artifact."""
        import os
        import tempfile
        from ..models.base import Model
        with tempfile.TemporaryDirectory() as d:
            p = os.path.join(d, "model.bin")
            with open(p, "wb") as f:
                f.write(raw)
            m = Model.load(p)
        return {"models": [_model_schema(m.key, m)]}

    # --------------------------------------------------------------- explain
    def varimp(self, key: str) -> dict:
        """GET /3/Models/{model}/varimp — variable importances."""
        from ..runtime import dkv
        from ..explain import _varimp_of
        m = dkv.get(key)
        if m is None:
            raise KeyError(f"no model {key!r}")
        vi = _varimp_of(m) or {}
        return {"varimp": [{"variable": k, "relative_importance": float(v)}
                           for k, v in vi.items()]}

    def partial_dependence(self, **params) -> dict:
        """POST /3/PartialDependence — PD table for one column."""
        from ..runtime import dkv
        from ..explain import partial_dependence as pd_fn
        clean = self._coerce(params)
        m = dkv.get(clean["model"])
        fr = dkv.get(clean["frame"])
        if m is None or fr is None:
            raise KeyError("missing model or frame")
        out = pd_fn(m, fr, clean["column"],
                    nbins=int(clean.get("nbins", 20)))
        return {"partial_dependence": {
            k: (v.tolist() if hasattr(v, "tolist") else v)
            for k, v in out.items()}}

    # -------------------------------------------------------------- builders
    def model_builders(self, algo: Optional[str] = None) -> dict:
        """GET /3/ModelBuilders[/{algo}] — algo list + parameter metadata
        (water/api/ModelBuildersHandler; drives client codegen)."""
        schemas = {s["algo"]: s for s in self.schemas()["schemas"]}
        if algo is not None:
            a = algo.lower()
            if a not in schemas:
                raise KeyError(f"unknown algo {algo!r}")
            return {"model_builders": {a: schemas[a]}}
        return {"model_builders": schemas}

    # ------------------------------------------------------------------ jobs
    def jobs_list(self) -> dict:
        from ..runtime import dkv
        from ..runtime.job import MIRROR_PREFIX, list_jobs
        out = [j.describe() for j in list_jobs()]
        seen = {d["key"] for d in out}
        # plain status mirrors replicated from other members' jobs
        for k in dkv.keys(MIRROR_PREFIX):
            d = dkv.get(k)
            if isinstance(d, dict) and d.get("key") not in seen:
                out.append(d)
        return {"jobs": out}

    # -------------------------------------------- small utility handlers
    # (the reference's RequestServer breadth: Typeahead, CreateFrame,
    #  MissingInserter, Interactions, Tabulate, DCTTransformer, JStack,
    #  NetworkTest — water/api/*Handler.java)
    def typeahead(self, src: str = "", limit: int = 100) -> dict:
        """GET /3/Typeahead/files — filesystem path completion."""
        import glob as _glob
        import os as _os
        limit = int(limit)
        pat = src + "*" if not src.endswith("*") else src
        matches = sorted(_glob.glob(_os.path.expanduser(pat)))[:limit]
        return {"src": src, "limit": limit, "matches": matches}

    def create_frame(self, **params) -> dict:
        from ..frame.create import create_frame
        fr = create_frame(**self._coerce(params))
        return {"key": {"name": fr.key}, **_frame_schema(fr.key, fr)}

    def missing_inserter(self, dataset: str, fraction: float = 0.1,
                         seed=None) -> dict:
        from ..frame.create import insert_missing_values
        from ..runtime import dkv
        fr = dkv.get(dataset)
        if fr is None:
            raise KeyError(f"no frame {dataset!r}")
        out = insert_missing_values(
            fr, fraction=float(fraction),
            seed=int(seed) if seed is not None else None)
        return {"key": {"name": out.key}, **_frame_schema(out.key, out)}

    def interaction(self, source_frame: str, factor_columns,
                    **params) -> dict:
        from ..frame.create import interaction
        from ..runtime import dkv
        fr = dkv.get(source_frame)
        if fr is None:
            raise KeyError(f"no frame {source_frame!r}")
        if isinstance(factor_columns, str):
            factor_columns = [c for c in factor_columns.split(",") if c]
        out = interaction(fr, factor_columns, **self._coerce(params))
        return {"key": {"name": out.key}, **_frame_schema(out.key, out)}

    def tabulate(self, dataset: str, predictor: str, response: str,
                 **params) -> dict:
        from ..frame.create import tabulate
        from ..runtime import dkv
        fr = dkv.get(dataset)
        if fr is None:
            raise KeyError(f"no frame {dataset!r}")
        return tabulate(fr, predictor, response, **self._coerce(params))

    def dct_transform(self, dataset: str, dimensions,
                      **params) -> dict:
        from ..frame.create import dct_transform
        from ..runtime import dkv
        fr = dkv.get(dataset)
        if fr is None:
            raise KeyError(f"no frame {dataset!r}")
        if isinstance(dimensions, str):
            dimensions = [int(x) for x in dimensions.split(",") if x]
        out = dct_transform(fr, dimensions, **self._coerce(params))
        return {"key": {"name": out.key}, **_frame_schema(out.key, out)}

    def jstack(self) -> dict:
        from ..runtime.observability import jstack
        return {"traces": jstack()}

    def network_test(self) -> dict:
        from ..runtime.observability import network_test
        return {"results": network_test()}

    # ------------------------------------------------------------------- dkv
    def remove(self, key: str) -> dict:
        from ..runtime import dkv
        dkv.remove(key)
        return {"removed": key}

    # ---------------------------------------------------------------- rapids
    def rapids(self, ast: str, **kw) -> dict:
        """POST /99/Rapids — evaluate a Rapids expression (Rapids.java:29)."""
        from ..rapids.ast import rapids as _eval
        from ..frame.frame import Frame
        out = _eval(ast)
        if isinstance(out, Frame):
            return {"key": {"name": out.key},
                    **_frame_schema(out.key or "", out)}
        if out is None:
            return {"result": None}
        if isinstance(out, (int, float)):
            return {"scalar": out}
        return {"string": str(out)}

    def about(self) -> dict:
        """GET /3/About — effective config + extensions (AboutHandler)."""
        from ..runtime.config import config
        from ..runtime.extensions import loaded
        from .. import __version__
        return {"version": __version__, "config": config().describe(),
                "extensions": loaded()}

    # -------------------------------------------------------------- metadata
    def schemas(self) -> dict:
        """GET /3/Metadata/schemas — parameter schemas for client codegen
        (the h2o-bindings gen_python.py contract)."""
        import dataclasses
        out = []
        for algo in ALGOS:
            try:
                cls = _builder(algo)
                pcls = cls(**{}).params.__class__
            except Exception:
                import inspect
                sig = inspect.signature(_builder(algo).__init__)
                pcls = None
            fields = []
            if pcls is not None:
                for f in dataclasses.fields(pcls):
                    default = f.default
                    if default is dataclasses.MISSING:
                        default = None
                    fields.append({
                        "name": f.name,
                        "type": getattr(f.type, "__name__", str(f.type)),
                        "default": default
                        if isinstance(default, (int, float, str, bool,
                                                type(None))) else
                        list(default) if isinstance(default, (list, tuple))
                        else str(default),
                    })
            out.append({"algo": algo, "parameters": fields})
        # grid-level parameters (GridSearch's own knobs, not per-model
        # hyperparameters) — introspected so client codegen tracks the
        # server, exactly like the builder schemas above
        import inspect
        from ..models.grid import GridSearch
        gfields = []
        for name, p in inspect.signature(
                GridSearch.__init__).parameters.items():
            if name in ("self", "builder_cls", "hyper_params",
                        "base_params") or p.kind in (
                    inspect.Parameter.VAR_KEYWORD,
                    inspect.Parameter.VAR_POSITIONAL):
                continue
            default = (None if p.default is inspect.Parameter.empty
                       else p.default)
            gfields.append({
                "name": name,
                "type": type(default).__name__ if default is not None
                else "object",
                "default": default if isinstance(
                    default, (int, float, str, bool, type(None)))
                else str(default)})
        return {"schemas": out, "grid": {"parameters": gfields}}

    # --------------------------------------------------------------- export
    def frame_summary(self, key: str) -> dict:
        from ..runtime import dkv
        fr = dkv.get(key)
        if fr is None:
            raise KeyError(f"no frame {key!r}")
        return {"frames": [{**_frame_schema(key, fr),
                            "summary": fr.summary()}]}

    def frame_data(self, key: str, row_offset=0, row_count=100, **kw) -> dict:
        """GET /3/Frames/{k}/data — paged column data (Flow grid contract)."""
        from ..runtime import dkv
        fr = dkv.get(key)
        if fr is None:
            raise KeyError(f"no frame {key!r}")
        lo = int(row_offset)
        hi = min(fr.nrows, lo + int(row_count))
        cols = {}
        for n, v in zip(fr.names, fr.vecs):
            col = v.decoded()[lo:hi]
            cols[n] = [None if (x is None or (isinstance(x, float)
                                              and np.isnan(x))) else x
                       for x in col.tolist()]
        return {"frame_id": {"name": key}, "row_offset": lo,
                "row_count": hi - lo, "data": cols}

    def export_frame(self, key: str, path: str, **kw) -> dict:
        from ..runtime import dkv
        from ..frame.parse import export_file
        fr = dkv.get(key)
        if fr is None:
            raise KeyError(f"no frame {key!r}")
        export_file(fr, path)
        return {"job": {"status": "DONE"}, "path": path}

    def import_files(self, path: str, **kw) -> dict:
        """GET /3/ImportFiles — expand globs/dirs (ImportFilesHandler)."""
        from ..frame.parse import _expand_paths
        files = _expand_paths(path)
        return {"files": files, "destination_frames": files}

    # ------------------------------------------------ round-5 route breadth
    def frame_columns(self, key: str) -> dict:
        """GET /3/Frames/{id}/columns (FramesHandler.columns)."""
        from ..runtime import dkv
        fr = dkv.get(key)
        if fr is None:
            raise KeyError(f"no frame {key!r}")
        cols = []
        for n, v in zip(fr.names, fr.vecs):
            cols.append({"label": n, "type": v.type,
                         "domain": v.domain,
                         "missing_count": int(v.rollups().nmissing)
                         if v.is_numeric or v.type == "cat" else 0})
        return {"frame_id": {"name": key}, "columns": cols}

    def frame_column_summary(self, key: str, col: str) -> dict:
        """GET /3/Frames/{id}/columns/{col}/summary."""
        from ..runtime import dkv
        fr = dkv.get(key)
        if fr is None:
            raise KeyError(f"no frame {key!r}")
        v = fr.vec(col)
        out = {"label": col, "type": v.type, "domain": v.domain}
        if v.is_numeric:
            r = v.rollups()
            out.update({"mins": [r.vmin], "maxs": [r.vmax], "mean": r.mean,
                        "sigma": r.sigma, "missing_count": r.nmissing})
        return {"frames": [{"columns": [out]}]}

    def frame_light(self, key: str) -> dict:
        """GET /3/Frames/{id}/light — metadata without data preview."""
        from ..runtime import dkv
        fr = dkv.get(key)
        if fr is None:
            raise KeyError(f"no frame {key!r}")
        return {"frames": [{"frame_id": {"name": key}, "rows": fr.nrows,
                            "column_count": fr.ncols,
                            "columns": [{"label": n} for n in fr.names]}]}

    def download_dataset(self, frame_id: str, **kw) -> bytes:
        """GET /3/DownloadDataset — frame as CSV bytes."""
        import io as _io
        from ..runtime import dkv
        from ..frame.parse import export_file
        fr = dkv.get(frame_id)
        if fr is None:
            raise KeyError(f"no frame {frame_id!r}")
        import tempfile
        with tempfile.NamedTemporaryFile(suffix=".csv", delete=False) as f:
            tmp = f.name
        try:
            export_file(fr, tmp)
            return open(tmp, "rb").read()
        finally:
            os.unlink(tmp)

    def model_java(self, key: str) -> bytes:
        """GET /3/Models.java/{id} — POJO source download."""
        from ..runtime import dkv
        from ..export.pojo import export_pojo
        m = dkv.get(key)
        if m is None:
            raise KeyError(f"no model {key!r}")
        import tempfile
        with tempfile.NamedTemporaryFile(suffix=".java",
                                         delete=False) as f:
            tmp = f.name
        try:
            export_pojo(m, tmp)
            return open(tmp, "rb").read()
        finally:
            os.unlink(tmp)

    def model_metrics_stored(self, key: str) -> dict:
        """GET /3/ModelMetrics/models/{id} — training/cv metrics."""
        from ..runtime import dkv
        m = dkv.get(key)
        if m is None:
            raise KeyError(f"no model {key!r}")
        out = []
        for kind, mm in (("training", m.training_metrics),
                         ("validation", m.validation_metrics),
                         ("cross_validation",
                          m.cross_validation_metrics)):
            if mm is None:
                continue
            d = mm.describe() if hasattr(mm, "describe") else (
                mm if isinstance(mm, dict) else {})
            out.append({"kind": kind,
                        **{k: v for k, v in d.items()
                           if isinstance(v, (int, float, str))}})
        return {"model_metrics": out}

    def word2vec_synonyms(self, model: str, word: str,
                          count: int = 20, **kw) -> dict:
        """GET /3/Word2VecSynonyms (Word2VecHandler.findSynonyms)."""
        from ..runtime import dkv
        m = dkv.get(model)
        if m is None:
            raise KeyError(f"no model {model!r}")
        syn = m.find_synonyms(word, int(count))
        return {"synonyms": list(syn.keys()),
                "scores": [float(s) for s in syn.values()]}

    def word2vec_transform(self, model: str, words_frame: str,
                           aggregate_method: str = "NONE", **kw) -> dict:
        """GET /3/Word2VecTransform — embed a string column."""
        from ..runtime import dkv
        m = dkv.get(model)
        fr = dkv.get(words_frame)
        if m is None or fr is None:
            raise KeyError(f"missing {model!r} or {words_frame!r}")
        out = m.transform(fr, aggregate_method=aggregate_method.lower())
        out.key = dkv.make_key("w2v_transform")
        dkv.put(out.key, out)
        return {"vectors_frame": {"name": out.key}}

    def grid_export(self, key: str, export_dir: str, **kw) -> dict:
        """POST /99/Grids/{id}/export (GridImportExportHandler)."""
        from ..runtime import dkv
        g = dkv.get(key)
        if g is None:
            raise KeyError(f"no grid {key!r}")
        g.save(f"{export_dir.rstrip('/')}/{key}")
        return {"grid_id": key, "export_dir": export_dir}

    def grid_import(self, grid_path: str, **kw) -> dict:
        """POST /99/Grids.bin/import."""
        from ..models.grid import Grid
        g = Grid.load(grid_path)
        return {"grid_id": g.key, "n_models": len(g.models)}

    def capabilities(self) -> dict:
        """GET /3/Capabilities (CapabilitiesHandler)."""
        from ..runtime.extensions import loaded
        return {"capabilities": [{"name": e} for e in loaded()]}

    def endpoints(self) -> dict:
        """GET /3/Metadata/endpoints — the live route table."""
        out = []
        for verb, table in (("GET", _Handler.routes_get),
                            ("POST", _Handler.routes_post),
                            ("DELETE", _Handler.routes_delete)):
            for pat in table:
                out.append({"http_method": verb, "url_pattern": pat})
        return {"routes": out, "count": len(out)}

    def init_id(self) -> dict:
        """GET /3/InitID — session handshake (h2o-py connection boot)."""
        import uuid
        return {"session_key": f"_sid_{uuid.uuid4().hex[:12]}"}

    def session_start(self) -> dict:
        """POST /4/sessions (the /4 tier session API)."""
        import uuid
        return {"session_key": f"_sid_{uuid.uuid4().hex[:12]}"}

    def ping(self) -> dict:
        """GET /3/Ping — liveness + cloud health (PingHandler)."""
        from ..runtime.cluster import cluster
        cl = cluster()
        return {"cloud_healthy": True,
                "n_devices": len(getattr(cl, "devices", []) or [1])}

    def garbage_collect(self) -> dict:
        """POST /3/GarbageCollect (GarbageCollectHandler)."""
        import gc
        gc.collect()
        import jax
        jax.clear_caches()
        return {"status": "done"}

    def log_and_echo(self, message: str = "", **kw) -> dict:
        """POST /3/LogAndEcho — write into the server log."""
        from ..runtime.observability import record
        record("log_and_echo", message=message)
        return {"message": message}

    def recovery_resume(self, recovery_dir: str, **kw) -> dict:
        """POST /3/Recovery/resume (RecoveryHandler — Recovery.java:72)."""
        from ..runtime.recovery import resume
        resumed = resume(recovery_dir)
        return {"resumed": [getattr(m, "key", str(m)) for m in resumed]}

    def recovery_status(self, recovery_dir: str = "", **kw) -> dict:
        """GET /3/Recovery — journal + progress-snapshot state: which jobs
        are resumable, from which snapshot/cursor (operator view of the
        survivable-training pipeline; defaults to H2O3_TPU_RECOVERY_DIR)."""
        from ..runtime import dkv
        from ..runtime.recovery import journal_status
        entries = journal_status(recovery_dir or None)
        return {"recovery_dir": recovery_dir or
                os.environ.get("H2O3_TPU_RECOVERY_DIR", ""),
                "entries": entries,
                "resumable": sum(1 for e in entries
                                 if e.get("status") == "running"),
                # resumes that silently weren't: entries whose frame
                # re-import failed and trained from scratch (or skipped)
                "downgraded": sum(1 for e in entries if e.get("downgrade")),
                # coordinator durability/fencing: epoch, WAL generation/
                # records, dedup window — the restart-runbook facts
                "coordinator": dkv.wal_stats()}

    def scheduler_status(self, **kw) -> dict:
        """GET /3/Scheduler — the cluster scheduler's live view: chip
        capacity/usage, admission queue, running assignments with
        budgets and per-tenant fair-share usage, elastic-membership
        state (known hosts, armed rebuild) and the flap quarantine."""
        from ..runtime.job import scheduler
        return {"scheduler": scheduler().describe()}

    _nps: dict = {}

    def nps_put(self, category: str, name: str, value: str = "",
                **kw) -> dict:
        """POST /3/NodePersistentStorage/{cat}/{name}."""
        self._nps[(category, name)] = value
        return {"category": category, "name": name}

    def nps_get(self, category: str, name: str) -> dict:
        """GET /3/NodePersistentStorage/{cat}/{name}."""
        if (category, name) not in self._nps:
            raise KeyError(f"no NPS entry {category}/{name}")
        return {"category": category, "name": name,
                "value": self._nps[(category, name)]}

    def nps_list(self, category: str) -> dict:
        """GET /3/NodePersistentStorage/{cat}."""
        return {"entries": [{"category": c, "name": n}
                            for (c, n) in self._nps
                            if c == category]}

    def import_sql_table(self, connection_url: str, table: str = "",
                         select_query: str = "", username: str = "",
                         password: str = "", **kw) -> dict:
        """POST /99/ImportSQLTable (water/jdbc SQLManager analog)."""
        from ..frame.sql import import_sql_table
        fr = import_sql_table(connection_url, table=table or None,
                              select_query=select_query or None,
                              username=username or None,
                              password=password or None)
        return {"frames": [{"frame_id": {"name": fr.key}}]}

    def frame_chunks(self, key: str) -> dict:
        """GET /3/FrameChunks — per-shard row layout (ChunkSummary)."""
        from ..runtime import dkv
        fr = dkv.get(key)
        if fr is None:
            raise KeyError(f"no frame {key!r}")
        from ..runtime.cluster import cluster
        cl = cluster()
        ndev = max(len(cl.mesh.devices.flat), 1) \
            if hasattr(cl, "mesh") else 1
        per = -(-fr.nrows // ndev)
        chunks = [{"chunk_id": i,
                   "row_count": min(per, max(fr.nrows - i * per, 0))}
                  for i in range(ndev)]
        return {"frame_id": {"name": key}, "chunks": chunks}

    def shutdown(self, **kw) -> dict:
        """POST /3/Shutdown — the reference stops the cloud; here the
        server thread stops accepting after the in-flight reply."""
        import threading as _t
        srv = getattr(self, "_server_ref", None)
        if srv is not None:
            _t.Thread(target=srv.stop, daemon=True).start()
        return {"status": "shutting down"}

    def timeline(self, limit=500, **kw) -> dict:
        """GET /3/Timeline[?limit=N] — recent runtime events
        (TimelineHandler:12) plus the monotonic counters (WAL records/
        bytes, dedup hits), per-node sections built from the telemetry
        shipped on heartbeat stamps, and span events stitched into trace
        trees (local + shipped, matched by trace_id)."""
        from ..runtime import observability as obs
        limit = int(limit)
        events = obs.timeline_events(limit)
        nodes = {}
        all_events = list(events)
        try:
            me = obs.node_name()
            for node, stamp in obs.cluster_stamps().items():
                if not isinstance(stamp, dict):
                    continue
                shipped = stamp.get("events") or []
                nodes[node] = {
                    "ts": stamp.get("ts"),
                    "pid": stamp.get("pid"),
                    "metric_series": len(stamp.get("metrics") or []),
                    "events": shipped[-limit:] if node != me else [],
                }
                if node != me:
                    all_events.extend(shipped)
        except Exception:                # noqa: BLE001 — local-only view
            pass
        return {"events": events, "counters": obs.counters(),
                "nodes": nodes, "traces": obs.trace_forest(all_events)}

    def prometheus(self) -> str:
        """GET /metrics — Prometheus text exposition: this process's
        registry plus every heartbeating node's shipped snapshot.

        Device-memory gauges refresh at SCRAPE time (not just on
        heartbeat beats), so ``device_memory_bytes`` is current however
        infrequently the beat thread runs."""
        from ..runtime import cluster
        from ..runtime.observability import render_prometheus
        try:
            cluster.sample_memory_gauges()
        except Exception:                # noqa: BLE001 — scrape never 500s
            pass
        return render_prometheus(cluster=True)

    def profiler_start(self, logdir: str = "", **kw) -> dict:
        """POST /3/Profiler/start — begin an on-demand jax.profiler device
        trace (TensorBoard-viewable).  Idempotent: a start while a capture
        is live is a recorded no-op, not a 500."""
        from ..runtime import observability as obs
        if not logdir:
            logdir = os.path.join(tempfile.gettempdir(),
                                  f"h2o3_tpu_trace_{os.getpid()}")
        started = obs.start_device_trace(logdir)
        return {"started": started, "active": obs.profiler_active(),
                "logdir": logdir}

    def profiler_stop(self, **kw) -> dict:
        """POST /3/Profiler/stop — stop the live device trace (no-op when
        none is running).  ``idle_by_span`` is the trace's first reading:
        the device's idle seconds by the program span that was open."""
        from ..runtime import observability as obs
        stopped = obs.stop_device_trace()
        return {"stopped": stopped, "active": obs.profiler_active(),
                "idle_by_span": obs.profiler_summary() if stopped else None}

    def profiler_memory(self) -> bytes:
        """GET /3/Profiler/memory — pprof-format device memory profile
        (``jax.profiler.device_memory_profile``), served as octet-stream."""
        import jax.profiler
        return jax.profiler.device_memory_profile()

    def compile_ledger(self) -> dict:
        """GET /3/Profiler/compiles — the compile ledger as JSON (same
        data the ``compile_seconds``/``program_*`` series expose)."""
        from ..runtime import xprof
        return xprof.ledger_snapshot()

    def autotune_table(self) -> dict:
        """GET /3/Profiler/autotune — the autotuner's decision table:
        program signature -> chosen knobs, decision source, and
        predicted vs measured tree-phase seconds."""
        from ..runtime import autotune
        return autotune.decision_table()

    def logs(self, limit=500, **kw) -> dict:
        from ..runtime.observability import recent_logs
        return {"log": recent_logs(int(limit))}

    def job(self, key: str) -> dict:
        from ..runtime.job import list_jobs
        for j in list_jobs():
            if j.key == key:
                return {"jobs": [j.describe()]}
        raise KeyError(f"no job {key!r}")

    def model_metrics(self, model_key: str, frame_key: str, **kw) -> dict:
        from ..runtime import dkv
        m = dkv.get(model_key)
        fr = dkv.get(frame_key)
        if m is None or fr is None:
            raise KeyError(f"missing {model_key!r} or {frame_key!r}")
        perf = m.model_performance(fr)
        d = perf.describe() if hasattr(perf, "describe") else {}
        return {"model_metrics": [{k: v for k, v in d.items()
                                   if isinstance(v, (int, float, str))}]}

    def scoring_history(self, model_key: str) -> dict:
        from ..runtime import dkv
        m = dkv.get(model_key)
        if m is None:
            raise KeyError(f"no model {model_key!r}")
        return {"scoring_history": getattr(m, "scoring_history", [])}

    def split_frame(self, key: str, ratios="[0.75]", seed=0,
                    **kw) -> dict:
        from ..runtime import dkv
        fr = dkv.get(key)
        if fr is None:
            raise KeyError(f"no frame {key!r}")
        rr = json.loads(ratios) if isinstance(ratios, str) else ratios
        pieces = fr.split_frame([float(r) for r in rr], seed=int(seed))
        out = []
        for i, p in enumerate(pieces):
            k = f"{key}_part{i}"
            p.key = k
            dkv.put(k, p)
            out.append(k)
        return {"destination_frames": out}


class H2OServer:
    """In-process REST server — H2OApp/Jetty boot analog.

    ``auth`` is an api.auth SPI spec ("static:u:p", "hash_file:/path",
    "cmd:/bin/verifier", "module:pkg.attr") or an Authenticator instance;
    default comes from env ``H2O3_TPU_AUTH``.  ``https=True`` wraps the
    listener in TLS using ``https_cert``/``https_key`` PEMs or, absent
    those, the internode TLS pair (H2O3_TPU_TLS_CERT/KEY) — the
    client-facing counterpart of h2o-security's Jetty HTTPS flags.
    """

    def __init__(self, port: Optional[int] = None, username: str = "",
                 password: str = "", auth=None, https: bool = False,
                 https_cert: Optional[str] = None,
                 https_key: Optional[str] = None):
        from . import auth as _authmod
        self.api = Api()
        if password and not username:
            raise ValueError("basic auth requires a username with the "
                             "password")
        if auth is None and username:
            auth = _authmod.StaticAuthenticator(username, password)
        if auth is None and os.environ.get("H2O3_TPU_AUTH"):
            auth = os.environ["H2O3_TPU_AUTH"]
        self._authn = _authmod.resolve_authenticator(auth)
        self._sessions = _authmod.SessionStore()
        self._https = https or bool(https_cert)
        self._https_cert, self._https_key = https_cert, https_key
        _Handler.routes_get = {
            r"/3/Cloud": lambda a: a.cloud(),
            r"/3/Frames": lambda a: a.frames(),
            r"/3/Frames/([^/]+)": lambda a, k: a.frame(k),
            r"/3/Frames/([^/]+)/summary": lambda a, k: a.frame_summary(k),
            r"/3/Frames/([^/]+)/data": lambda a, k, **kw:
                a.frame_data(k, **kw),
            r"/3/Models": lambda a: a.models(),
            r"/3/Models/([^/]+)": lambda a, k: a.model(k),
            r"/3/Models/([^/]+)/scoring_history": lambda a, k:
                a.scoring_history(k),
            r"/3/Models/([^/]+)/varimp": lambda a, k: a.varimp(k),
            r"/3/Models/([^/]+)/mojo": lambda a, k: a.model_fetch_mojo(k),
            r"/3/Models\.fetch\.bin/([^/]+)": lambda a, k:
                a.model_fetch_bin(k),
            r"/3/ModelBuilders": lambda a: a.model_builders(),
            r"/3/ModelBuilders/([^/]+)": lambda a, algo:
                a.model_builders(algo),
            r"/99/Grids": lambda a: a.grids(),
            r"/99/Grids/([^/]+)": lambda a, k: a.grid(k),
            r"/99/Leaderboards/([^/]+)": lambda a, p: a.leaderboard(p),
            r"/3/Jobs": lambda a: a.jobs_list(),
            r"/3/Jobs/([^/]+)": lambda a, k: a.job(k),
            r"/3/ImportFiles": lambda a, **kw: a.import_files(**kw),
            r"/3/Metadata/schemas": lambda a: a.schemas(),
            r"/3/About": lambda a: a.about(),
            r"/3/Timeline": lambda a, **kw: a.timeline(**kw),
            r"/3/Logs": lambda a, **kw: a.logs(**kw),
            r"/metrics": lambda a: a.prometheus(),
            r"/3/Typeahead/files": lambda a, **kw: a.typeahead(**kw),
            r"/3/JStack": lambda a: a.jstack(),
            r"/3/NetworkTest": lambda a: a.network_test(),
            r"/3/Frames/([^/]+)/columns": lambda a, k: a.frame_columns(k),
            r"/3/Frames/([^/]+)/columns/([^/]+)/summary":
                lambda a, k, c: a.frame_column_summary(k, c),
            r"/3/Frames/([^/]+)/light": lambda a, k: a.frame_light(k),
            r"/3/DownloadDataset": lambda a, **kw:
                a.download_dataset(**kw),
            r"/3/Models\.java/([^/]+)": lambda a, k: a.model_java(k),
            r"/3/ModelMetrics/models/([^/]+)":
                lambda a, k: a.model_metrics_stored(k),
            r"/3/Word2VecSynonyms": lambda a, **kw:
                a.word2vec_synonyms(**kw),
            r"/3/Word2VecTransform": lambda a, **kw:
                a.word2vec_transform(**kw),
            r"/3/Capabilities": lambda a: a.capabilities(),
            r"/3/Metadata/endpoints": lambda a: a.endpoints(),
            r"/3/InitID": lambda a: a.init_id(),
            r"/3/Ping": lambda a: a.ping(),
            r"/3/NodePersistentStorage/([^/]+)/([^/]+)":
                lambda a, c, n: a.nps_get(c, n),
            r"/3/NodePersistentStorage/([^/]+)":
                lambda a, c: a.nps_list(c),
            r"/3/FrameChunks/([^/]+)": lambda a, k: a.frame_chunks(k),
            r"/3/Recovery": lambda a, **kw: a.recovery_status(**kw),
            r"/3/Scheduler": lambda a, **kw: a.scheduler_status(**kw),
            r"/3/Profiler/memory": lambda a: a.profiler_memory(),
            r"/3/Profiler/compiles": lambda a: a.compile_ledger(),
            r"/3/Profiler/autotune": lambda a: a.autotune_table(),
        }
        _Handler.routes_post = {
            r"/3/Parse": lambda a, **kw: a.parse(**kw),
            r"/3/ModelBuilders/([^/]+)": lambda a, algo, **kw:
                a.train(algo, **kw),
            r"/3/Predictions/models/([^/]+)/frames/([^/]+)":
                lambda a, m, f, **kw: a.predict(m, f, **kw),
            r"/3/Predictions/realtime/([^/]+)":
                lambda a, m, **kw: a.predict_realtime(m, **kw),
            r"/3/Predictions/realtime/([^/]+)/warmup":
                lambda a, m, **kw: a.publish_realtime(m, **kw),
            r"/99/Rapids": lambda a, **kw: a.rapids(**kw),
            r"/3/Frames/([^/]+)/export": lambda a, k, **kw:
                a.export_frame(k, **kw),
            r"/3/ModelMetrics/models/([^/]+)/frames/([^/]+)":
                lambda a, m, f, **kw: a.model_metrics(m, f, **kw),
            r"/3/SplitFrame": lambda a, **kw: a.split_frame(**kw),
            r"/99/Grid/([^/]+)": lambda a, algo, **kw:
                a.grid_train(algo, **kw),
            r"/99/AutoMLBuilder": lambda a, **kw: a.automl_build(**kw),
            r"/99/Models\.bin/([^/]+)": lambda a, k, **kw:
                a.model_save(k, **kw),
            r"/3/PartialDependence": lambda a, **kw:
                a.partial_dependence(**kw),
            r"/3/CreateFrame": lambda a, **kw: a.create_frame(**kw),
            r"/3/MissingInserter": lambda a, **kw:
                a.missing_inserter(**kw),
            r"/3/Interaction": lambda a, **kw: a.interaction(**kw),
            r"/99/Tabulate": lambda a, **kw: a.tabulate(**kw),
            r"/99/DCTTransformer": lambda a, **kw: a.dct_transform(**kw),
            r"/99/Grids/([^/]+)/export": lambda a, k, **kw:
                a.grid_export(k, **kw),
            r"/99/Grids\.bin/import": lambda a, **kw: a.grid_import(**kw),
            r"/4/sessions": lambda a, **kw: a.session_start(),
            r"/3/GarbageCollect": lambda a, **kw: a.garbage_collect(),
            r"/3/LogAndEcho": lambda a, **kw: a.log_and_echo(**kw),
            r"/3/Recovery/resume": lambda a, **kw:
                a.recovery_resume(**kw),
            r"/3/NodePersistentStorage/([^/]+)/([^/]+)":
                lambda a, c, n, **kw: a.nps_put(c, n, **kw),
            r"/99/ImportSQLTable": lambda a, **kw:
                a.import_sql_table(**kw),
            r"/3/Shutdown": lambda a, **kw: a.shutdown(**kw),
            r"/3/Profiler/start": lambda a, **kw: a.profiler_start(**kw),
            r"/3/Profiler/stop": lambda a, **kw: a.profiler_stop(**kw),
        }
        _Handler.routes_delete = {
            r"/3/DKV/([^/]+)": lambda a, k: a.remove(k),
        }
        if port is None:
            from ..runtime.config import config
            port = config().port
        self.httpd = _Server(("127.0.0.1", port), _Handler)
        self.httpd.api = self.api
        self.api._server_ref = self
        self.httpd.authenticator = self._authn
        self.httpd.sessions = self._sessions
        if self._https:
            import ssl
            from ..runtime.config import config
            cert = self._https_cert or config().tls_cert
            key = self._https_key or config().tls_key
            if not (cert and key):
                raise ValueError(
                    "https=True needs https_cert/https_key PEMs or "
                    "H2O3_TPU_TLS_CERT/H2O3_TPU_TLS_KEY in the env")
            ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
            ctx.load_cert_chain(cert, key)
            # per-connection wrap with a deferred handshake: the TLS
            # handshake then runs in the HANDLER thread (first read),
            # not the accept loop — one stalled client cannot freeze
            # the listener (the handler's socket timeout bounds it)
            self.httpd.ssl_context = ctx
        self.port = self.httpd.server_address[1]
        self._thread: Optional[threading.Thread] = None

    def start(self) -> "H2OServer":
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    def stop(self):
        from ..runtime.config import config
        self.httpd.shutdown()               # stop accepting new requests
        # bounded drain: in-flight handlers get to finish their reply
        # (the /3/Shutdown response itself rides this grace window)
        left = self.httpd.drain(config().rest_drain_timeout_s)
        if left:
            from ..runtime.observability import log
            log.warning("REST shutdown: %d request handler(s) still "
                        "running after %.1fs drain", left,
                        config().rest_drain_timeout_s)
        self.httpd.server_close()

    @property
    def url(self) -> str:
        scheme = "https" if self._https else "http"
        return f"{scheme}://127.0.0.1:{self.port}"


def start_server(port: int = 0, username: str = "", password: str = "",
                 **kw) -> H2OServer:
    """Boot the REST layer on an in-process runtime (port 0 = ephemeral).

    Extra keywords (auth=, https=, https_cert=, https_key=) pass through
    to H2OServer — see its docstring for the authn/TLS surface."""
    return H2OServer(port=port, username=username,
                     password=password, **kw).start()
