"""Web UI server + remote stats routing.

Equivalent of ui/play/PlayUIServer.java (RoutingDsl routes :112-155, port
:274), api/UIServer.java SPI, module/train/TrainModule.java (overview/model
pages), module/remote/RemoteReceiverModule.java, and core
api/storage/impl/RemoteUIStatsStorageRouter.java:1-355 (HTTP POST of stats
to a remote UI).

The Play framework is replaced by stdlib http.server on a daemon thread;
charts render client-side from the JSON endpoints with inline JS (no
external assets — zero-egress friendly).
"""

from __future__ import annotations

import json
import logging
import threading
import urllib.parse
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List, Optional

from deeplearning4j_tpu.ui.stats import StatsReport
from deeplearning4j_tpu.ui.storage import StatsStorage

log = logging.getLogger(__name__)


def _num(v):
    """Lenient float coercion — reports/histograms may come from untrusted
    remote POSTs, and one malformed value must not kill a whole route."""
    try:
        return None if v is None else float(v)
    except (TypeError, ValueError):
        return None


def _int(v, default: int = 0) -> int:
    try:
        return int(v)
    except (TypeError, ValueError):
        return default


_CHART_JS = """
// shared canvas plotting for all tabs (served at /chart.js)
function drawSeries(cv, series){
  const ctx = cv.getContext('2d');
  ctx.clearRect(0,0,cv.width,cv.height);
  let xs=[], ys=[];
  series.forEach(s=>{s.pts.forEach(p=>{xs.push(p[0]); ys.push(p[1]);});});
  if(!xs.length) return;
  const xmin=Math.min(...xs), xmax=Math.max(...xs,xmin+1);
  const ymin=Math.min(...ys), ymax=Math.max(...ys,ymin+1e-12);
  const X=x=>40+(x-xmin)/(xmax-xmin)*(cv.width-60);
  const Y=y=>cv.height-25-(y-ymin)/(ymax-ymin)*(cv.height-45);
  ctx.strokeStyle='#999';ctx.strokeRect(40,20,cv.width-60,cv.height-45);
  ctx.fillStyle='#333';ctx.font='11px sans-serif';
  ctx.fillText(ymax.toPrecision(4),2,25);
  ctx.fillText(ymin.toPrecision(4),2,cv.height-25);
  ctx.fillText(String(xmax),cv.width-40,cv.height-8);
  const colors=['#1976d2','#e53935','#43a047','#fb8c00','#8e24aa','#00897b'];
  series.forEach((s,i)=>{
    ctx.strokeStyle=colors[i%colors.length];ctx.beginPath();
    s.pts.forEach((p,j)=>{j?ctx.lineTo(X(p[0]),Y(p[1])):ctx.moveTo(X(p[0]),Y(p[1]))});
    ctx.stroke();
    ctx.fillStyle=colors[i%colors.length];
    ctx.fillText(s.name,50+i*150,14);
  });
}
function drawHist(cv, bins, counts){
  const ctx=cv.getContext('2d');ctx.clearRect(0,0,cv.width,cv.height);
  if(!counts||!counts.length)return;
  const cmax=Math.max(...counts,1);
  const bw=(cv.width-60)/counts.length;
  ctx.fillStyle='#1976d2';
  counts.forEach((c,i)=>{
    const h=c/cmax*(cv.height-45);
    ctx.fillRect(40+i*bw,cv.height-25-h,bw-1,h);
  });
  ctx.fillStyle='#333';ctx.font='11px sans-serif';
  ctx.fillText(bins[0].toPrecision(3),40,cv.height-8);
  ctx.fillText(bins[bins.length-1].toPrecision(3),cv.width-60,cv.height-8);
}
"""

_PAGE = """<!DOCTYPE html>
<html><head><title>deeplearning4j_tpu training UI</title>
<style>
body{font-family:sans-serif;margin:20px;background:#fafafa}
h1{font-size:20px} h2{font-size:16px;margin-top:24px}
.chart{border:1px solid #ccc;background:#fff;margin:8px 0}
#meta{color:#555;font-size:13px}
table{border-collapse:collapse;font-size:13px}
td,th{border:1px solid #ddd;padding:4px 8px}
</style></head>
<body>
<h1>Training overview</h1>
<div id="meta"></div>
<h2>Score vs iteration</h2>
<canvas id="score" class="chart" width="900" height="260"></canvas>
<h2>Parameter mean magnitudes</h2>
<canvas id="pmm" class="chart" width="900" height="260"></canvas>
<h2>Performance</h2>
<table id="perf"></table>
<script src="/chart.js"></script>
<script>
async function refresh(){
  const sessions = await (await fetch('/train/sessions')).json();
  if(!sessions.length) return;
  const sid = sessions[sessions.length-1];
  const ov = await (await fetch('/train/overview?sid='+
                    encodeURIComponent(sid))).json();
  document.getElementById('meta').textContent =
    'session '+sid+' — '+(ov.modelClass||'?')+', '+
    (ov.numParams||'?')+' params, '+ov.scores.length+' reports';
  drawSeries(document.getElementById('score'),
    [{name:'score',pts:ov.scores}]);
  const pseries = Object.entries(ov.paramMeanMagnitudes).slice(0,6)
    .map(([k,v])=>({name:k,pts:v}));
  drawSeries(document.getElementById('pmm'), pseries);
  const perf=document.getElementById('perf');
  perf.replaceChildren();
  const hdr=perf.insertRow(), row=perf.insertRow();
  [['last iteration',ov.lastIteration],
   ['iter time (ms)',ov.lastIterTimeMs],
   ['memory RSS (MB)',ov.memoryRssMb]].forEach(([h,v])=>{
    const th=document.createElement('th'); th.textContent=h;
    hdr.appendChild(th);
    row.insertCell().textContent=(v==null)?'-':String(v);
  });
}
refresh(); setInterval(refresh, 3000);
</script></body></html>
"""

_MODEL_PAGE = """<!DOCTYPE html>
<html><head><title>model — deeplearning4j_tpu UI</title>
<style>
body{font-family:sans-serif;margin:20px;background:#fafafa}
h1{font-size:20px} h2{font-size:16px;margin-top:24px}
.chart{border:1px solid #ccc;background:#fff;margin:8px 0}
#meta{color:#555;font-size:13px}
select{margin:8px 0}
</style></head>
<body>
<h1>Model — per-layer parameters</h1>
<div id="meta"></div>
<select id="layer"></select>
<h2>Mean magnitudes vs iteration</h2>
<canvas id="mm" class="chart" width="900" height="260"></canvas>
<h2>Parameter histogram (latest)</h2>
<canvas id="hist" class="chart" width="900" height="260"></canvas>
<script src="/chart.js"></script>
<script>
let currentLayer=null;
async function refresh(){
  const sessions=await (await fetch('/train/sessions')).json();
  if(!sessions.length)return;
  const sid=sessions[sessions.length-1];
  const layers=await (await fetch('/train/model/layers?sid='+
                      encodeURIComponent(sid))).json();
  const sel=document.getElementById('layer');
  if(sel.options.length!=layers.length){
    sel.replaceChildren();
    layers.forEach(l=>{const o=document.createElement('option');
      o.value=l;o.textContent=l;sel.appendChild(o);});
    sel.onchange=()=>{currentLayer=sel.value;refresh();};
  }
  const layer=currentLayer||layers[0];
  if(!layer)return;
  const d=await (await fetch('/train/model/data/'+
      encodeURIComponent(layer)+'?sid='+encodeURIComponent(sid))).json();
  document.getElementById('meta').textContent=
    'session '+sid+' — layer '+layer;
  drawSeries(document.getElementById('mm'),
    Object.entries(d.meanMagnitudes).map(([k,v])=>({name:k,pts:v})));
  const hk=Object.keys(d.histograms);
  if(hk.length){const h=d.histograms[hk[0]];
    drawHist(document.getElementById('hist'),h.bins,h.counts);}
}
refresh(); setInterval(refresh, 3000);
</script></body></html>
"""

_SYSTEM_PAGE = """<!DOCTYPE html>
<html><head><title>system — deeplearning4j_tpu UI</title>
<style>
body{font-family:sans-serif;margin:20px;background:#fafafa}
h1{font-size:20px} h2{font-size:16px;margin-top:24px}
.chart{border:1px solid #ccc;background:#fff;margin:8px 0}
table{border-collapse:collapse;font-size:13px}
td,th{border:1px solid #ddd;padding:4px 8px}
</style></head>
<body>
<h1>System</h1>
<h2>Memory RSS (MB) vs iteration</h2>
<canvas id="mem" class="chart" width="900" height="220"></canvas>
<h2>Iteration time (ms)</h2>
<canvas id="it" class="chart" width="900" height="220"></canvas>
<h2>Software / hardware</h2>
<table id="sw"></table>
<script src="/chart.js"></script>
<script>
async function refresh(){
  const sessions=await (await fetch('/train/sessions')).json();
  if(!sessions.length)return;
  const sid=sessions[sessions.length-1];
  const d=await (await fetch('/train/system/data?sid='+
                  encodeURIComponent(sid))).json();
  drawSeries(document.getElementById('mem'),
    [{name:'rss',pts:d.memory}]);
  drawSeries(document.getElementById('it'),
    [{name:'iter ms',pts:d.iterationTimesMs}]);
  const t=document.getElementById('sw');t.replaceChildren();
  Object.entries(d.software).forEach(([k,v])=>{
    const r=t.insertRow();
    const th=document.createElement('th');th.textContent=k;
    r.appendChild(th);r.insertCell().textContent=String(v);
  });
}
refresh(); setInterval(refresh, 3000);
</script></body></html>
"""

_ACTIVATIONS_PAGE = """<!DOCTYPE html>
<html><head><title>activations — deeplearning4j_tpu UI</title>
<style>
body{font-family:sans-serif;margin:20px;background:#fafafa}
h1{font-size:20px} #meta{color:#555;font-size:13px}
img{border:1px solid #ccc;background:#fff;margin:8px;image-rendering:
pixelated}
</style></head>
<body>
<h1>Convolutional activations</h1>
<div id="meta"></div>
<div id="grids"></div>
<script>
async function refresh(){
  const d=await (await fetch('/activations/data')).json();
  if(!d.sessions.length){document.getElementById('meta').textContent=
    'no activations published yet';return;}
  const sid=d.sessions[d.sessions.length-1];
  const info=d.info[sid];
  document.getElementById('meta').textContent=
    'session '+sid+' — iteration '+info.iteration;
  const g=document.getElementById('grids');g.replaceChildren();
  info.layers.forEach(l=>{
    const img=document.createElement('img');
    img.src='/activations/img?sid='+encodeURIComponent(sid)+
            '&layer='+l+'&it='+info.iteration;
    g.appendChild(img);
  });
}
refresh(); setInterval(refresh, 3000);
</script></body></html>
"""

_TSNE_PAGE = """<!DOCTYPE html>
<html><head><title>t-SNE — deeplearning4j_tpu UI</title>
<style>
body{font-family:sans-serif;margin:20px;background:#fafafa}
h1{font-size:20px} #meta{color:#555;font-size:13px}
canvas{border:1px solid #ccc;background:#fff}
</style></head>
<body>
<h1>t-SNE plot</h1>
<div id="meta"></div>
<canvas id="plot" width="800" height="800"></canvas>
<script>
async function refresh(){
  const sids = await (await fetch('/tsne/sessions')).json();
  if(!sids.length){document.getElementById('meta').textContent=
    'no t-SNE data uploaded (POST /tsne/upload)'; return;}
  const sid = sids[sids.length-1];
  const d = await (await fetch('/tsne/coords?sid='+
                   encodeURIComponent(sid))).json();
  document.getElementById('meta').textContent =
    'session '+sid+' — '+d.coords.length+' points';
  const cv=document.getElementById('plot'), ctx=cv.getContext('2d');
  ctx.clearRect(0,0,cv.width,cv.height);
  const xs=d.coords.map(p=>p[0]), ys=d.coords.map(p=>p[1]);
  const xmin=Math.min(...xs), xmax=Math.max(...xs,xmin+1e-9);
  const ymin=Math.min(...ys), ymax=Math.max(...ys,ymin+1e-9);
  const X=x=>20+(x-xmin)/(xmax-xmin)*(cv.width-40);
  const Y=y=>cv.height-20-(y-ymin)/(ymax-ymin)*(cv.height-40);
  ctx.font='10px sans-serif'; ctx.fillStyle='#1976d2';
  d.coords.forEach((p,i)=>{
    ctx.beginPath();ctx.arc(X(p[0]),Y(p[1]),2,0,6.3);ctx.fill();
    if(d.labels && d.labels[i]!=null)
      ctx.fillText(String(d.labels[i]),X(p[0])+3,Y(p[1])-3);
  });
}
refresh(); setInterval(refresh, 5000);
</script></body></html>
"""


class _Handler(BaseHTTPRequestHandler):
    server_version = "dl4jtpu-ui/0.1"

    def log_message(self, fmt, *args):  # quiet
        log.debug("ui: " + fmt, *args)

    def _json(self, obj, code=200):
        body = json.dumps(obj).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def _html(self, page: str):
        body = page.encode()
        self.send_response(200)
        self.send_header("Content-Type", "text/html")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        storages: List[StatsStorage] = self.server.storages
        path, _, query = self.path.partition("?")
        params = {k: v[0] for k, v in
                  urllib.parse.parse_qs(query).items()}
        if path in ("/", "/train", "/train/overview.html"):
            return self._html(_PAGE)
        # Prometheus scrape endpoint: the global telemetry registry
        # (monitoring/) in text exposition format. Runtime gauges
        # (RSS/HBM) refresh per scrape but never initialize a backend —
        # same rule as the system tab below.
        if path == "/metrics":
            from deeplearning4j_tpu.monitoring import exporters
            body = exporters.render_prometheus().encode()
            self.send_response(200)
            self.send_header("Content-Type", exporters.CONTENT_TYPE)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        # the structured ops timeline (monitoring/events.py), JSON:
        # ?n=<count> bounds the tail, ?category=<serving|fleet|...>
        # filters. The ring is snapshotted under its lock and serialized
        # OUTSIDE it — a slow client can never stall an emitter.
        if path == "/events":
            from deeplearning4j_tpu.monitoring import events as ev
            elog = ev.global_event_log()
            try:
                n = max(0, int(params.get("n", 200)))
            except ValueError:
                return self._json({"error": "n must be an integer"}, 400)
            tail = elog.tail(n, category=params.get("category"))
            return self._json({
                "depth": elog.depth(),
                "dropped": elog.dropped_total,
                "enabled": ev.events_enabled(),
                "events": [e.as_dict() for e in tail]})
        # liveness/health probe beside /metrics and /events: every
        # attached health probe (an engine's or fleet router's
        # ``health()`` callable) dumped as JSON, HTTP 200 only while
        # every component reports healthy (503 otherwise — so a load
        # balancer can act on the status code without parsing)
        if path == "/health":
            probes = getattr(self.server, "health_probes", {})
            components, ok = {}, True
            for name, probe in sorted(probes.items()):
                try:
                    payload = probe()
                except Exception as e:  # noqa: BLE001 — report, don't die
                    components[name] = {"error": repr(e)}
                    ok = False
                    continue
                components[name] = payload
                healthy = payload.get("healthy") \
                    if isinstance(payload, dict) else None
                if healthy is False:
                    ok = False
            return self._json(
                {"healthy": ok, "components": components},
                200 if ok else 503)
        if path == "/chart.js":
            body = _CHART_JS.encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/javascript")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if path == "/train/sessions":
            sids = sorted({s for st in storages for s in st.list_session_ids()})
            return self._json(sids)
        if path == "/train/overview":
            sid = params.get("sid")
            if sid is None:
                return self._json({"error": "sid required"}, 400)
            return self._json(self._overview(storages, sid))
        # model tab (ref: TrainModule.java:98-104 — /train/model,
        # /train/model/data/:layerId, /train/model/graph)
        if path in ("/train/model", "/train/model/"):
            return self._html(_MODEL_PAGE)
        if path == "/train/model/layers":
            sid = params.get("sid")
            if sid is None:
                return self._json({"error": "sid required"}, 400)
            return self._json(self._layer_ids(storages, sid))
        if path.startswith("/train/model/data"):
            sid = params.get("sid")
            if sid is None:
                return self._json({"error": "sid required"}, 400)
            layer_id = urllib.parse.unquote(
                path[len("/train/model/data"):].lstrip("/"))
            layer_id = params.get("layerId", layer_id)
            return self._json(self._model_data(storages, sid, layer_id))
        # system tab (ref: TrainModule.java:105-116 — /train/system,
        # /train/system/data)
        if path in ("/train/system", "/train/system/"):
            return self._html(_SYSTEM_PAGE)
        if path == "/train/system/data":
            sid = params.get("sid")
            if sid is None:
                return self._json({"error": "sid required"}, 400)
            return self._json(self._system_data(storages, sid))
        # evaluation results stored via the router (eval/serde round-trip)
        if path == "/train/evaluations":
            sid = params.get("sid")
            if sid is None:
                return self._json({"error": "sid required"}, 400)
            out = []
            for st in storages:
                try:
                    out.extend(st.get_evaluations(sid))
                except NotImplementedError:
                    pass
            return self._json(out)
        # conv-activations tab (ref: ConvolutionalListenerModule.java:47 —
        # /activations serves the latest tiled grids)
        if path in ("/activations", "/activations/"):
            return self._html(_ACTIVATIONS_PAGE)
        if path == "/activations/data":
            # snapshot: the fit thread may insert sessions mid-iteration
            acts = dict(self.server.activation_sessions)
            return self._json({
                "sessions": sorted(acts),
                "info": {sid: {"iteration": a["iteration"],
                               "layers": sorted(a["pngs"])}
                         for sid, a in acts.items()}})
        if path == "/activations/img":
            sid = params.get("sid")
            a = self.server.activation_sessions.get(sid)
            try:
                layer = int(params.get("layer", -1))
            except ValueError:
                layer = -1
            png = (a or {}).get("pngs", {}).get(layer)
            if png is None:
                return self._json({"error": "no such activation"}, 404)
            self.send_response(200)
            self.send_header("Content-Type", "image/png")
            self.send_header("Content-Length", str(len(png)))
            self.end_headers()
            self.wfile.write(png)
            return
        # t-SNE module (ref: ui/module/tsne/TsneModule.java — upload +
        # per-session coordinate plots)
        if path in ("/tsne", "/tsne/"):
            body = _TSNE_PAGE.encode()
            self.send_response(200)
            self.send_header("Content-Type", "text/html")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
            return
        if path == "/tsne/sessions":
            return self._json(list(self.server.tsne_sessions))
        if path == "/tsne/coords":
            sid = params.get("sid")
            data = self.server.tsne_sessions.get(sid)
            if data is None:
                return self._json({"error": f"unknown session {sid!r}"}, 404)
            return self._json(data)
        self._json({"error": "not found"}, 404)

    def do_POST(self):
        path = self.path.partition("?")[0].rstrip("/")
        # t-SNE upload (ref: TsneModule.java POST /tsne/upload/:sid)
        if path == "/tsne/upload":
            try:
                n = int(self.headers.get("Content-Length", 0))
                payload = json.loads(self.rfile.read(n) or b"{}")
                if not isinstance(payload, dict):
                    raise ValueError("body must be a JSON object")
                sid = str(payload.get("sessionId", "uploaded"))
                coords = [[float(a), float(b)]
                          for a, b in payload["coords"]]
                labels = payload.get("labels")
                if labels is not None:
                    labels = [str(l) for l in labels]
                    if len(labels) != len(coords):
                        raise ValueError("labels/coords length mismatch")
            except (KeyError, TypeError, ValueError) as e:
                return self._json({"error": f"malformed payload: {e}"}, 400)
            self.server.tsne_sessions[sid] = {"coords": coords,
                                              "labels": labels}
            return self._json({"status": "ok", "sessionId": sid})
        # remote stats receiver (ref: RemoteReceiverModule.java)
        if path != "/remoteReceive":
            return self._json({"error": "not found"}, 404)
        if not self.server.remote_enabled:
            return self._json({"error": "remote receiver disabled"}, 403)
        if not self.server.storages:
            return self._json({"error": "no storage attached"}, 503)
        storage = self.server.storages[0]
        try:
            n = int(self.headers.get("Content-Length", 0))
            payload = json.loads(self.rfile.read(n) or b"{}")
            kind = payload.get("type")
            if kind == "staticInfo":
                storage.put_static_info(str(payload["sessionId"]),
                                        dict(payload["data"]))
            elif kind == "update":
                storage.put_update(StatsReport.from_dict(payload["data"]))
            elif kind == "evaluation":
                # eval/serde JSON rides the same remote route and is
                # reloadable via GET /train/evaluations + eval_from_dict
                storage.put_evaluation(str(payload["sessionId"]),
                                       dict(payload["data"]))
            else:
                return self._json({"error": f"unknown type {kind!r}"}, 400)
        except (KeyError, TypeError, ValueError) as e:
            return self._json({"error": f"malformed payload: {e}"}, 400)
        self._json({"status": "ok"})

    @staticmethod
    def _updates(storages: List[StatsStorage], sid: str) -> List[StatsReport]:
        updates: List[StatsReport] = []
        for st in storages:
            updates.extend(st.get_all_updates(sid))
        updates.sort(key=lambda r: r.iteration)
        return updates

    @classmethod
    def _layer_ids(cls, storages, sid) -> List[str]:
        """Top-level param-tree groups ("layer0", "layer1", ...) seen in any
        report — the :layerId values of the model tab."""
        layers = set()
        for r in cls._updates(storages, sid):
            for k in list(r.param_mean_magnitudes) + \
                    list(r.param_histograms):
                layers.add(str(k).split(".", 1)[0])
        return sorted(layers)

    @classmethod
    def _model_data(cls, storages, sid, layer_id: str) -> dict:
        """Per-layer time series + latest histograms (ref:
        TrainModule.getModelData :~400 — mean magnitude chart, activations,
        learning rates, param histograms per layer)."""
        def match(name: str) -> bool:
            return not layer_id or name == layer_id or \
                str(name).startswith(layer_id + ".")

        mm: dict = {}
        umm: dict = {}
        hists: dict = {}
        for r in cls._updates(storages, sid):
            for k, v in r.param_mean_magnitudes.items():
                if match(str(k)):
                    mm.setdefault(str(k), []).append(
                        [_int(r.iteration), _num(v)])
            for k, v in r.update_mean_magnitudes.items():
                if match(str(k)):
                    umm.setdefault(str(k), []).append(
                        [_int(r.iteration), _num(v)])
            for k, h in r.param_histograms.items():
                if match(str(k)) and isinstance(h, dict):
                    hists[str(k)] = {          # latest wins
                        "iteration": _int(r.iteration),
                        "bins": [_num(b) for b in h.get("bins", [])],
                        "counts": [_int(c) for c in h.get("counts", [])]}
        return {"sessionId": sid, "layerId": layer_id,
                "meanMagnitudes": mm, "updateMeanMagnitudes": umm,
                "histograms": hists}

    @classmethod
    def _system_data(cls, storages, sid) -> dict:
        """Memory/timing series + software info (ref: TrainModule
        /train/system/data — JVM memory, hardware, software tables)."""
        mem, itms, sps = [], [], []
        for r in cls._updates(storages, sid):
            it = _int(r.iteration)
            if r.memory_rss_mb is not None:
                mem.append([it, _num(r.memory_rss_mb)])
            if r.iteration_time_ms is not None:
                itms.append([it, _num(r.iteration_time_ms)])
            if r.samples_per_sec is not None:
                sps.append([it, _num(r.samples_per_sec)])
        import platform as _platform

        import jax as _jax
        import numpy as _np
        software = {"python": _platform.python_version(),
                    "jax": _jax.__version__,
                    "numpy": _np.__version__,
                    "platform": _platform.platform()}
        # device info only if a backend is ALREADY initialized —
        # default_backend() would otherwise initialize one, and a UI
        # route must never be the thing that first touches (and so
        # claims for this process) the accelerator
        from deeplearning4j_tpu.monitoring.runtime import (
            backend_initialized)
        if backend_initialized():
            software["backend"] = _jax.default_backend()
            software["deviceCount"] = _jax.device_count()
        return {"sessionId": sid, "memory": mem,
                "iterationTimesMs": itms, "samplesPerSec": sps,
                "software": software}

    @staticmethod
    def _overview(storages: List[StatsStorage], sid: str) -> dict:
        static = None
        updates: List[StatsReport] = []
        for st in storages:
            static = static or st.get_static_info(sid)
            updates.extend(st.get_all_updates(sid))
        updates.sort(key=lambda r: r.iteration)

        pmm: dict = {}
        for r in updates:
            for k, v in r.param_mean_magnitudes.items():
                pmm.setdefault(str(k), []).append([_int(r.iteration), _num(v)])
        last = updates[-1] if updates else None
        return {
            "sessionId": sid,
            "modelClass": str((static or {}).get("modelClass") or "")[:200],
            "numParams": _num((static or {}).get("numParams")),
            "scores": [[_int(r.iteration), _num(r.score)] for r in updates],
            "paramMeanMagnitudes": pmm,
            "lastIteration": _int(last.iteration) if last else None,
            "lastIterTimeMs": _num(last.iteration_time_ms) if last else None,
            "memoryRssMb": _num(last.memory_rss_mb) if last else None,
        }


class UIServer:
    """Singleton UI server (ref: api/UIServer.java — getInstance(),
    attach(statsStorage), enableRemoteListener())."""

    _instance: Optional["UIServer"] = None

    def __init__(self, port: int = 9000):
        self._httpd = ThreadingHTTPServer(("127.0.0.1", port), _Handler)
        self._httpd.storages = []
        self._httpd.remote_enabled = False
        self._httpd.tsne_sessions = {}
        self._httpd.activation_sessions = {}
        self._httpd.health_probes = {}
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        log.info("UI server at http://127.0.0.1:%d/train", self.port)

    @classmethod
    def get_instance(cls, port: int = 9000) -> "UIServer":
        if cls._instance is None:
            cls._instance = UIServer(port)
        return cls._instance

    def attach(self, storage: StatsStorage) -> None:
        if storage not in self._httpd.storages:
            self._httpd.storages.append(storage)

    def detach(self, storage: StatsStorage) -> None:
        if storage in self._httpd.storages:
            self._httpd.storages.remove(storage)

    def attach_health(self, name: str, probe) -> None:
        """Register a component under the ``/health`` endpoint:
        `probe` is a zero-arg callable returning a JSON-able dict (an
        engine's or fleet router's ``health()``). A dict carrying
        ``healthy: False`` — or a probe that raises — turns the
        endpoint's status into 503."""
        self._httpd.health_probes[name] = probe

    def detach_health(self, name: str) -> None:
        self._httpd.health_probes.pop(name, None)

    def upload_tsne(self, coords, labels=None,
                    session_id: str = "uploaded") -> None:
        """Publish 2-D t-SNE coordinates to the /tsne tab (ref:
        TsneModule.uploadFile — here arrays instead of a coord file;
        pair with plot.tsne.Tsne/BarnesHutTsne.fit_transform)."""
        import numpy as _np
        c = _np.asarray(coords, float)
        if c.ndim != 2 or c.shape[1] < 2:
            raise ValueError("coords must be [N, 2+]")
        data = {"coords": c[:, :2].tolist(),
                "labels": None if labels is None
                else [str(l) for l in labels]}
        if data["labels"] is not None and len(data["labels"]) != len(c):
            raise ValueError("labels/coords length mismatch")
        self._httpd.tsne_sessions[session_id] = data

    def publish_activations(self, session_id: str, iteration: int,
                            grids) -> None:
        """Publish conv activation grids to the /activations tab (ref:
        ConvolutionalListenerModule.java:47). `grids` is a list of
        (layer_index, [H,W] uint8 array); the latest iteration replaces the
        previous one, like the reference's single-image tab."""
        from deeplearning4j_tpu.ui.convolutional import encode_png_gray
        pngs = {int(li): encode_png_gray(g) for li, g in grids}
        self._httpd.activation_sessions[session_id] = {
            "iteration": int(iteration), "pngs": pngs}

    def enable_remote_listener(self, storage: Optional[StatsStorage] = None):
        """ref: UIServer.enableRemoteListener — POSTs to /remoteReceive land
        in the first attached storage (or the one given here); with no
        storage at all an InMemoryStatsStorage is created, like the
        reference."""
        if storage is not None:
            # atomic list swap: handler threads index storages[0] and must
            # never observe a transiently-empty list
            self._httpd.storages = [storage] + [
                s for s in self._httpd.storages if s is not storage]
        elif not self._httpd.storages:
            from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
            self._httpd.storages.append(InMemoryStatsStorage())
        self._httpd.remote_enabled = True

    def stop(self) -> None:
        self._httpd.shutdown()
        self._thread.join(timeout=5)
        self._httpd.server_close()  # release the listening socket
        if UIServer._instance is self:
            UIServer._instance = None


class RemoteUIStatsStorageRouter(StatsStorage):
    """Client that routes stats to a remote UIServer over HTTP POST
    (ref: core api/storage/impl/RemoteUIStatsStorageRouter.java:1-355 —
    retry with backoff on failure; here: bounded retries, then drop+warn)."""

    def __init__(self, url: str, retries: int = 3, timeout: float = 5.0):
        self.url = url.rstrip("/") + "/remoteReceive"
        self.retries = retries
        self.timeout = timeout

    def _post(self, payload: dict) -> bool:
        data = json.dumps(payload).encode()
        for attempt in range(self.retries):
            try:
                req = urllib.request.Request(
                    self.url, data=data,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=self.timeout) as r:
                    return r.status == 200
            except Exception as e:  # noqa: BLE001
                if attempt == self.retries - 1:
                    log.warning("remote stats post failed: %s", e)
        return False

    def put_static_info(self, session_id, info):
        self._post({"type": "staticInfo", "sessionId": session_id,
                    "data": info})

    def put_update(self, report: StatsReport):
        self._post({"type": "update", "data": report.to_dict()})

    def put_evaluation(self, session_id, eval_dict):
        """POST an eval/serde dict to the remote UI; reload it with
        GET /train/evaluations + eval_from_dict."""
        self._post({"type": "evaluation", "sessionId": session_id,
                    "data": eval_dict})

    # remote router is write-only (ref: RemoteUIStatsStorageRouter is a
    # StatsStorageRouter, not a StatsStorage)
    def list_session_ids(self):
        return []

    def get_static_info(self, session_id):
        return None

    def get_all_updates(self, session_id):
        return []

    def get_evaluations(self, session_id):
        return []
