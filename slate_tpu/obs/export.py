"""slateflight live exporter: OpenMetrics text + a scrape server.

Everything else in :mod:`slate_tpu.obs` is post-hoc (trace / snapshot
written at process exit, read by ``obs report``).  A serving process
needs the opposite: a live pull surface a Prometheus-shaped scraper
can hit *while* the solver is running.  This module renders the
metrics registry as `OpenMetrics text
<https://prometheus.io/docs/specs/om/open_metrics_spec/>`_ and serves
it from a stdlib ``http.server`` daemon thread:

* ``GET /metrics``  — the registry (counters → ``_total``, gauges,
  reservoir histograms → summaries with cumulative ``_count``/``_sum``
  and reservoir quantiles, exact log-bucket histograms → native
  cumulative-``_bucket{le=...}`` histograms, span aggregates →
  ``_calls_total`` + ``_seconds_total``), terminated by ``# EOF``;
* ``GET /healthz``  — liveness JSON wired to the numerical-health
  layer (``robust/guards`` recent HealthReports) and the backend
  ladder's demotion state — HTTP 503 once a ladder has demoted to its
  terminal ``<none>`` rung (the instance lost a capability class);
* ``GET /vars``     — the flop-enriched ``obs.dump()`` snapshot as
  JSON.

Arming: ``SLATE_TPU_METRICS_PORT=<port>`` at startup (also enables
the metrics registry — a live exporter over a dead registry scrapes
empty), or programmatically ``obs.serve_metrics(port=0)`` (0 = kernel
-assigned ephemeral port; the chosen one is on the returned handle).
The server binds loopback by default — exporting off-host is a
deployment decision (``SLATE_TPU_METRICS_HOST``), not a default.

The zero-overhead-off contract is untouched: nothing here is on any
solver path; an unarmed process never imports a socket.
"""

from __future__ import annotations

import json

from . import metrics as _metrics
from ..runtime import sync

ENV_PORT = "SLATE_TPU_METRICS_PORT"
ENV_HOST = "SLATE_TPU_METRICS_HOST"

CONTENT_TYPE = ("application/openmetrics-text; version=1.0.0; "
                "charset=utf-8")

# every exported series carries the stack's namespace so a shared
# scrape config can select slate_tpu_* without per-metric allowlists
PREFIX = "slate_tpu_"

_QUANTILES = (("0.5", "p50"), ("0.9", "p90"), ("0.99", "p99"))


def _num(v) -> str:
    f = float(v)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _labelset(labels: dict, extra: tuple = ()) -> str:
    items = [(_metrics.sanitize_label_name(k),
              _metrics.escape_label_value(v))
             for k, v in sorted(labels.items())]
    items.extend(extra)
    if not items:
        return ""
    return "{" + ",".join(f'{k}="{v}"' for k, v in items) + "}"


def render_openmetrics(snap: dict | None = None) -> str:
    """The registry as OpenMetrics text exposition (ends ``# EOF``).

    Families: counter ``<name>_total``; gauge ``<name>``; reservoir
    histogram → summary ``<name>`` (``_count``/``_sum`` cumulative
    over every observation, ``quantile`` samples from the bounded
    reservoir — see ``metrics.HIST_SAMPLE_CAP``); exact log-bucket
    histogram → native histogram with cumulative ``_bucket{le=...}``
    rows (ending ``le="+Inf"``) + ``_count``/``_sum``; span aggregate
    ``<name>`` → ``<name>_calls_total`` + ``<name>_seconds_total``
    counters.
    """
    if snap is None:
        snap = _metrics.snapshot()
    san = _metrics.sanitize_metric_name
    # family name -> (type, [sample lines]); insertion-ordered so the
    # output is deterministic given the (sorted) snapshot
    fams: dict[str, tuple[str, list[str]]] = {}

    def fam(name: str, mtype: str) -> list[str]:
        got = fams.get(name)
        if got is None:
            got = (mtype, [])
            fams[name] = got
        return got[1]

    for c in snap.get("counters", []):
        name = PREFIX + san(c["name"])
        fam(name, "counter").append(
            f"{name}_total{_labelset(c['labels'])} {_num(c['value'])}")
    for g in snap.get("gauges", []):
        name = PREFIX + san(g["name"])
        fam(name, "gauge").append(
            f"{name}{_labelset(g['labels'])} {_num(g['value'])}")
    for h in snap.get("histograms", []):
        name = PREFIX + san(h["name"])
        if h.get("kind") == "log" and h.get("buckets") is not None:
            # exact log-bucket series render as a NATIVE histogram:
            # cumulative _bucket{le=...} rows ending at le="+Inf"
            rows = fam(name, "histogram")
            cum = 0
            for le, c in h["buckets"]:
                cum += c
                rows.append(
                    f"{name}_bucket"
                    f"{_labelset(h['labels'], (('le', f'{le:.6g}'),))}"
                    f" {_num(cum)}")
            rows.append(
                f"{name}_bucket"
                f"{_labelset(h['labels'], (('le', '+Inf'),))}"
                f" {_num(h['count'])}")
        else:
            rows = fam(name, "summary")
            for q, key in _QUANTILES:
                if key in h:
                    rows.append(f"{name}{_labelset(h['labels'], (('quantile', q),))}"
                                f" {_num(h[key])}")
        rows.append(f"{name}_count{_labelset(h['labels'])} "
                    f"{_num(h['count'])}")
        rows.append(f"{name}_sum{_labelset(h['labels'])} "
                    f"{_num(h['sum'])}")
    for s in snap.get("spans", []):
        base = PREFIX + san(s["name"])
        calls = base + "_calls"
        secs = base + "_seconds"
        fam(calls, "counter").append(
            f"{calls}_total{_labelset(s['labels'])} {_num(s['count'])}")
        fam(secs, "counter").append(
            f"{secs}_total{_labelset(s['labels'])} "
            f"{_num(s['total_s'])}")

    lines: list[str] = []
    for name, (mtype, rows) in fams.items():
        lines.append(f"# TYPE {name} {mtype}")
        lines.extend(rows)
    lines.append("# EOF")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------------------------
# /healthz and /vars payloads
# ---------------------------------------------------------------------------

def healthz() -> tuple[int, dict]:
    """(http_status, body): 200 while every capability class still has
    a rung to run on; 503 once any ladder demoted to its terminal
    ``<none>`` rung.  Numerical-health failures (nonzero-``info``
    HealthReports) are surfaced but do not flip liveness — a singular
    input is the request's problem, not the instance's."""
    body: dict = {"status": "ok"}
    try:
        from ..robust import abft, guards, ladder
        demos = ladder.demotions_as_dicts()
        terminal = [d for d in demos if d.get("to_rung") == "<none>"]
        body["ladder"] = {"demotions": len(demos),
                          "terminal": len(terminal),
                          "log": demos[-8:]}
        if terminal:
            body["status"] = "no_backend"
        recent = guards.recent_reports()
        bad = [r for r in recent if not r.ok]
        body["health_reports"] = {
            "recent": len(recent), "recent_bad": len(bad),
            "bad_total": guards.bad_report_total(),
            "last_bad": bad[-1].as_dict() if bad else None}
        # abft (robust/abft.py): checksum-verification posture of the
        # recent reports.  ``verified is None`` means Option.Abft was
        # off for that run — only armed runs count either way.
        checked = [r for r in recent if r.verified is not None]
        failed = [r for r in checked if not r.verified]
        body["abft"] = {
            "checked": len(checked), "failed": len(failed),
            "detections": len(abft.detection_log()),
            "last_checked": (checked[-1].as_dict() if checked
                             else None)}
    except Exception as e:  # noqa: BLE001 — a health probe never 500s
        body["probe_error"] = f"{type(e).__name__}: {e}"
    try:
        from ..robust import faults
        body["faults_armed"] = [s.kind for s in faults.active()]
    except Exception:  # noqa: BLE001
        pass
    try:
        from . import correlation, flight
        body["rids_inflight"] = len(correlation.inflight())
        lb = flight.last_bundle()
        body["flight"] = {"enabled": flight.enabled(),
                          "last_trigger": lb["trigger"] if lb else None}
    except Exception:  # noqa: BLE001
        pass
    try:
        # serving posture (slatepulse): only when the serve layer is
        # already imported — a probe must not drag jax in
        import sys
        if "slate_tpu.serve.sched" in sys.modules:
            sv = sys.modules["slate_tpu.serve.sched"].serve_health()
            if sv is not None:
                body["serve"] = sv
    except Exception:  # noqa: BLE001
        pass
    return (200 if body["status"] == "ok" else 503), body


def vars_snapshot() -> dict:
    from . import dump
    return dump()


# ---------------------------------------------------------------------------
# the scrape server
# ---------------------------------------------------------------------------

class MetricsServer:
    """Handle on a running scrape server (``.port``, ``.url``,
    ``.stop()``)."""

    def __init__(self, server, thread):
        self._server = server
        self._thread = thread
        self.host, self.port = server.server_address[:2]

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        self._thread.join(timeout=5)


_server: MetricsServer | None = None
_server_lock = sync.Lock(name="obs.export.server")


def _make_handler():
    from http.server import BaseHTTPRequestHandler

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 — http.server API
            path = self.path.split("?", 1)[0]
            try:
                if path == "/metrics":
                    status, ctype = 200, CONTENT_TYPE
                    body = render_openmetrics().encode()
                elif path == "/healthz":
                    status, payload = healthz()
                    ctype = "application/json"
                    body = json.dumps(payload, indent=1,
                                      default=str).encode()
                elif path in ("/vars", "/varz"):
                    status, ctype = 200, "application/json"
                    body = json.dumps(vars_snapshot(), indent=1,
                                      default=str).encode()
                else:
                    status, ctype = 404, "text/plain"
                    body = b"slate_tpu: /metrics /healthz /vars\n"
            except Exception as e:  # noqa: BLE001 — scrape never kills
                status, ctype = 500, "text/plain"
                body = f"{type(e).__name__}: {e}\n".encode()
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):  # scrapes don't belong on stderr
            pass

    return Handler


def serve_metrics(port: int = 0, host: str | None = None) -> MetricsServer:
    """Start (or return the already-running) scrape server.  Enables
    the metrics registry — the exporter exists to be scraped.  With
    ``port=0`` the kernel assigns an ephemeral port; read it off the
    returned handle."""
    global _server
    with _server_lock:
        if _server is not None:
            return _server
        import os
        from http.server import ThreadingHTTPServer
        from . import metrics
        metrics.enable()
        if host is None:
            host = os.environ.get(ENV_HOST, "127.0.0.1")
        srv = ThreadingHTTPServer((host, port), _make_handler())
        srv.daemon_threads = True
        t = sync.Thread(target=srv.serve_forever,
                        name="slate-tpu-metrics", daemon=True)
        t.start()
        _server = MetricsServer(srv, t)
        return _server


def stop_metrics() -> None:
    """Shut the scrape server down (tests; production lets the daemon
    thread die with the process)."""
    global _server
    with _server_lock:
        if _server is not None:
            _server.stop()
            _server = None
