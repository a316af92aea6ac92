"""Static HTML training report from a StatsStorage.

Reference parity: the deeplearning4j-vertx dashboard's Overview and
Model tabs (VertxUIServer.java:78; TrainModule's score chart, update:
parameter ratio chart, histograms, system tab) rendered as ONE
self-contained HTML file: inline SVG, zero external assets, no server.
"""
from __future__ import annotations

import html as _html
from typing import List, Optional, Sequence, Tuple

from deeplearning4j_tpu.ui.stats import StatsStorage


def _svg_line(points: Sequence[Tuple[float, float]], w=640, h=180,
              color="#1f77b4", label="", ylog=False) -> str:
    if not points:
        return f"<p>(no data for {_html.escape(label)})</p>"
    import math
    xs = [p[0] for p in points]
    ys = [(math.log10(max(p[1], 1e-12)) if ylog else p[1]) for p in points]
    x0, x1 = min(xs), max(xs) or 1
    y0, y1 = min(ys), max(ys)
    if y1 - y0 < 1e-12:
        y0, y1 = y0 - 1, y1 + 1
    px = lambda x: 45 + (x - x0) / max(x1 - x0, 1e-12) * (w - 55)
    py = lambda y: (h - 25) - (y - y0) / (y1 - y0) * (h - 35)
    path = " ".join(f"{'M' if i == 0 else 'L'}{px(x):.1f},{py(y):.1f}"
                    for i, (x, y) in enumerate(zip(xs, ys)))
    fmt = (lambda v: f"1e{v:.1f}") if ylog else (lambda v: f"{v:.4g}")
    return f"""<svg width="{w}" height="{h}" style="background:#fafafa">
<text x="5" y="14" font-size="12" fill="#444">{_html.escape(label)}</text>
<text x="5" y="{h-28}" font-size="10" fill="#888">{fmt(y0)}</text>
<text x="5" y="26" font-size="10" fill="#888">{fmt(y1)}</text>
<path d="{path}" stroke="{color}" fill="none" stroke-width="1.5"/>
</svg>"""


def _svg_hist(hist: List[int], edges: List[float], w=220, h=90,
              label="") -> str:
    if not hist or max(hist) == 0:
        return ""
    n = len(hist)
    bw = (w - 10) / n
    mx = max(hist)
    bars = "".join(
        f'<rect x="{5+i*bw:.1f}" y="{(h-18)*(1-v/mx)+4:.1f}" '
        f'width="{bw-1:.1f}" height="{(h-18)*v/mx:.1f}" fill="#2ca02c"/>'
        for i, v in enumerate(hist))
    return f"""<svg width="{w}" height="{h}" style="background:#fafafa">
{bars}
<text x="5" y="{h-4}" font-size="9" fill="#666">{_html.escape(label)}
 [{edges[0]:.3g}, {edges[1]:.3g}]</text></svg>"""


_STAGE_COLORS = (("data_wait_s", "#1f77b4", "data wait"),
                 ("dispatch_s", "#ff7f0e", "dispatch"),
                 ("flush_s", "#2ca02c", "flush"),
                 ("other_s", "#9467bd", "other"))


def _svg_stack(rows: List[dict], w=640, h=200, label="") -> str:
    """Stacked per-flush bars of the step-time breakdown (one bar per
    {"type": "steptime"} record, stages stacked bottom-up)."""
    rows = [r for r in rows if r.get("steps")]
    if not rows:
        return f"<p>(no data for {_html.escape(label)})</p>"
    totals = [sum(r.get(k, 0.0) for k, _, _ in _STAGE_COLORS)
              for r in rows]
    mx = max(totals) or 1.0
    n = len(rows)
    bw = (w - 60) / n
    parts = [f'<svg width="{w}" height="{h}" style="background:#fafafa">',
             f'<text x="5" y="14" font-size="12" fill="#444">'
             f'{_html.escape(label)}</text>']
    for i, r in enumerate(rows):
        y = h - 22
        for key, color, _ in _STAGE_COLORS:
            v = r.get(key, 0.0)
            bh = (h - 45) * v / mx
            y -= bh
            parts.append(
                f'<rect x="{50 + i * bw:.1f}" y="{y:.1f}" '
                f'width="{max(bw - 1, 1):.1f}" height="{bh:.1f}" '
                f'fill="{color}"><title>{key[:-2]}: {v:.4f}s</title>'
                f'</rect>')
    parts.append(f'<text x="5" y="{h - 26}" font-size="10" fill="#888">'
                 f'0</text>')
    parts.append(f'<text x="5" y="30" font-size="10" fill="#888">'
                 f'{mx:.3g}s</text>')
    lx = 50
    for key, color, name in _STAGE_COLORS:
        parts.append(f'<rect x="{lx}" y="{h - 14}" width="10" height="10" '
                     f'fill="{color}"/>')
        parts.append(f'<text x="{lx + 13}" y="{h - 5}" font-size="10" '
                     f'fill="#444">{name}</text>')
        lx += 13 + 8 * len(name) + 14
    parts.append("</svg>")
    return "\n".join(parts)


def _svg_heatmap(matrix: List[List[float]], row_labels: List[str],
                 w=640, cell_h=18, label="", log10: bool = True) -> str:
    """Rows × columns heatmap (layers × samples), light→dark by value
    (log10 by default — grad norms span decades). NaN/zero cells render
    grey."""
    import math
    rows = [r for r in matrix if r]
    if not rows or not row_labels:
        return f"<p>(no data for {_html.escape(label)})</p>"
    vals = []
    for r in rows:
        for v in r:
            if v and v > 0 and math.isfinite(v):
                vals.append(math.log10(v) if log10 else v)
    if not vals:
        return f"<p>(no finite data for {_html.escape(label)})</p>"
    lo, hi = min(vals), max(vals)
    if hi - lo < 1e-12:
        lo, hi = lo - 1, hi + 1
    ncols = max(len(r) for r in rows)
    x0 = 130
    cw = (w - x0 - 10) / ncols
    h = 24 + cell_h * len(rows) + 18

    def color(v):
        if not v or v <= 0 or not math.isfinite(v):
            return "#ddd"
        t = ((math.log10(v) if log10 else v) - lo) / (hi - lo)
        # light blue -> dark navy ramp
        r0, g0, b0 = 0xdb, 0xe9, 0xf6
        r1, g1, b1 = 0x08, 0x30, 0x6b
        return "#%02x%02x%02x" % (round(r0 + t * (r1 - r0)),
                                  round(g0 + t * (g1 - g0)),
                                  round(b0 + t * (b1 - b0)))

    parts = [f'<svg width="{w}" height="{h}" style="background:#fafafa">',
             f'<text x="5" y="14" font-size="12" fill="#444">'
             f'{_html.escape(label)}</text>']
    for ri, (name, row) in enumerate(zip(row_labels, rows)):
        y = 22 + ri * cell_h
        parts.append(f'<text x="5" y="{y + cell_h - 5}" font-size="10" '
                     f'fill="#666">{_html.escape(str(name)[:18])}</text>')
        for ci, v in enumerate(row):
            parts.append(
                f'<rect x="{x0 + ci * cw:.1f}" y="{y}" '
                f'width="{max(cw - 1, 1):.1f}" height="{cell_h - 2}" '
                f'fill="{color(v)}"><title>{_html.escape(str(name))}'
                f'[{ci}]: {v:.4g}</title></rect>')
    lo10 = f"1e{lo:.1f}" if log10 else f"{lo:.3g}"
    hi10 = f"1e{hi:.1f}" if log10 else f"{hi:.3g}"
    parts.append(f'<text x="{x0}" y="{h - 4}" font-size="10" fill="#888">'
                 f'{lo10} (light) → {hi10} (dark)</text>')
    parts.append("</svg>")
    return "\n".join(parts)


def _span_color(name: str) -> str:
    # crc32, NOT builtin hash(): the name→color mapping must be stable
    # across processes (hash() is salted per run; reports rendered from
    # the same storage twice would recolor every lane)
    import zlib
    palette = ("#1f77b4", "#ff7f0e", "#2ca02c", "#d62728", "#9467bd",
               "#8c564b", "#e377c2", "#17becf", "#bcbd22", "#7f7f7f")
    return palette[zlib.crc32(name.encode("utf-8")) % len(palette)]


def _svg_swimlane(spans: List[dict], w=940, h_lane=26, label="",
                  max_spans=2000) -> str:
    """Span-timeline swimlane: one lane per thread, one rect per span
    (nesting shown by depth shading), hover for name/duration."""
    spans = [s for s in spans if s.get("dur", 0) > 0][:max_spans]
    if not spans:
        return f"<p>(no data for {_html.escape(label)})</p>"
    t0 = min(s["ts"] for s in spans)
    t1 = max(s["ts"] + s["dur"] for s in spans)
    total = max(t1 - t0, 1e-9)
    lanes: List[int] = []
    lane_names = {}
    for s in spans:
        if s["tid"] not in lanes:
            lanes.append(s["tid"])
            lane_names[s["tid"]] = s.get("thread") or str(s["tid"])
    # nesting depth per span (parent chain within the dump)
    by_sid = {s.get("sid"): s for s in spans if s.get("sid")}
    def depth(s):
        d, p = 0, s.get("parent")
        while p and p in by_sid and d < 8:
            d += 1
            p = by_sid[p].get("parent")
        return d
    h = 20 + h_lane * len(lanes) + 16
    px = lambda t: 120 + (t - t0) / total * (w - 130)
    parts = [f'<svg width="{w}" height="{h}" style="background:#fafafa">',
             f'<text x="5" y="14" font-size="12" fill="#444">'
             f'{_html.escape(label)} ({total:.3f}s)</text>']
    for li, tid in enumerate(lanes):
        y = 20 + li * h_lane
        nm = lane_names[tid][:16]
        parts.append(f'<text x="5" y="{y + 16}" font-size="10" '
                     f'fill="#666">{_html.escape(nm)}</text>')
        parts.append(f'<line x1="120" y1="{y + h_lane - 2}" x2="{w - 10}" '
                     f'y2="{y + h_lane - 2}" stroke="#eee"/>')
    for s in spans:
        li = lanes.index(s["tid"])
        d = depth(s)
        y = 20 + li * h_lane + 2 + d * 4
        x, bw = px(s["ts"]), max(0.6, s["dur"] / total * (w - 130))
        bh = max(3, h_lane - 8 - d * 4)
        tip = (f'{s["name"]} {1e3 * s["dur"]:.3f}ms'
               + (f' {s["args"]}' if s.get("args") else ""))
        parts.append(
            f'<rect x="{x:.1f}" y="{y}" width="{bw:.1f}" height="{bh}" '
            f'fill="{_span_color(s["name"])}" fill-opacity="0.8">'
            f'<title>{_html.escape(tip)}</title></rect>')
    parts.append("</svg>")
    return "\n".join(parts)


#: record types render_report knows how to draw; everything else lands
#: in the forward-compatibility footer instead of being dropped
_KNOWN_TYPES = frozenset({
    "meta", "score", "perf", "params", "memory", "end", "serving",
    "checkpoint", "dispatch", "faults", "metrics", "steptime", "trace",
    "compile", "reshard", "tensorstats", "memory_plan", "analysis",
    "datapipe", "integrity", "fleet"})


#: memory-plan byte components for the stacked budget chart, mirroring
#: monitor/memstats.PLAN_BYTE_FIELDS (colors match the steptime stack)
_PLAN_COLORS = (("argument_bytes", "#1f77b4", "arguments"),
                ("temp_bytes", "#ff7f0e", "temps"),
                ("output_bytes", "#2ca02c", "outputs"),
                ("generated_code_bytes", "#9467bd", "code"))


def _svg_budget(plans: List[dict], w=640, h=220, label="") -> str:
    """Stacked per-program memory-budget bars (one bar per captured
    plan: argument/temp/output/generated-code bytes stacked)."""
    plans = [p for p in plans
             if any(p.get(k) for k, _, _ in _PLAN_COLORS)]
    if not plans:
        return f"<p>(no data for {_html.escape(label)})</p>"

    def _component(p, key):
        v = p.get(key, 0) or 0
        if key == "argument_bytes":
            # donated/aliased bytes reuse argument space — subtract
            # them here so the bar height equals the plan's
            # total_bytes and the chart agrees with the table's
            # "total MiB" column
            v = max(0, v - (p.get("alias_bytes", 0) or 0))
        return v

    totals = [sum(_component(p, k) for k, _, _ in _PLAN_COLORS)
              for p in plans]
    mx = max(totals) or 1
    n = len(plans)
    bw = min(90, (w - 70) / n)
    parts = [f'<svg width="{w}" height="{h}" style="background:#fafafa">',
             f'<text x="5" y="14" font-size="12" fill="#444">'
             f'{_html.escape(label)}</text>']
    for i, p in enumerate(plans):
        y = h - 36
        x = 60 + i * bw
        for key, color, name in _PLAN_COLORS:
            v = _component(p, key)
            bh = (h - 60) * v / mx
            y -= bh
            parts.append(
                f'<rect x="{x:.1f}" y="{y:.1f}" '
                f'width="{max(bw - 3, 1):.1f}" height="{bh:.1f}" '
                f'fill="{color}"><title>{name}: {v / 2**20:.2f} MiB'
                f'</title></rect>')
        prog = str(p.get("program", "?"))[:12]
        parts.append(f'<text x="{x:.1f}" y="{h - 22}" font-size="9" '
                     f'fill="#666">{_html.escape(prog)}</text>')
    parts.append(f'<text x="5" y="30" font-size="10" fill="#888">'
                 f'{mx / 2**20:.1f} MiB</text>')
    lx = 60
    for _, color, name in _PLAN_COLORS:
        parts.append(f'<rect x="{lx}" y="{h - 14}" width="10" '
                     f'height="10" fill="{color}"/>')
        parts.append(f'<text x="{lx + 13}" y="{h - 5}" font-size="10" '
                     f'fill="#444">{name}</text>')
        lx += 13 + 7 * len(name) + 14
    parts.append("</svg>")
    return "\n".join(parts)


def render_report(storage: StatsStorage, title: str = "Training report"
                  ) -> str:
    scores = storage.of_type("score")
    perf = storage.of_type("perf")
    params = storage.of_type("params")
    memory = storage.of_type("memory")
    memory_plans = storage.of_type("memory_plan")
    oom_events = [r for r in storage.of_type("faults")
                  if r.get("event") == "oom"]
    end = storage.of_type("end")
    tensorstats = storage.of_type("tensorstats")
    steptime = [r for r in storage.of_type("steptime")
                if r.get("event") != "straggler"]
    stragglers = [r for r in storage.of_type("steptime")
                  if r.get("event") == "straggler"]
    traces = storage.of_type("trace")
    metrics = storage.of_type("metrics")
    compiles = storage.of_type("compile")
    analyses = storage.of_type("analysis")
    reshards = storage.of_type("reshard")
    datapipe = storage.of_type("datapipe")
    serving = storage.of_type("serving")
    fleet = storage.of_type("fleet")
    serving_faults = [r for r in storage.of_type("faults")
                      if r.get("origin") == "serving"]
    integrity = storage.of_type("integrity")
    stall_events = [r for r in storage.of_type("faults")
                    if r.get("event") == "stall"]

    parts = [f"""<!doctype html><html><head><meta charset="utf-8">
<title>{_html.escape(title)}</title>
<style>body{{font-family:sans-serif;margin:24px;color:#222}}
h2{{border-bottom:1px solid #ddd;padding-bottom:4px}}
.row{{display:flex;flex-wrap:wrap;gap:12px}}
table{{border-collapse:collapse;font-size:13px}}
td,th{{border:1px solid #ccc;padding:3px 8px}}</style></head><body>
<h1>{_html.escape(title)}</h1>"""]

    # -- overview: score + throughput ------------------------------------
    parts.append("<h2>Overview</h2><div class='row'>")
    parts.append(_svg_line([(r["iter"], r["loss"]) for r in scores],
                           label="score vs iteration", ylog=True))
    parts.append(_svg_line(
        [(r["iter"], r.get("samples_per_sec", r["batches_per_sec"]))
         for r in perf],
        label="throughput (samples/sec)" if any(
            "samples_per_sec" in r for r in perf)
        else "throughput (batches/sec)", color="#ff7f0e"))
    parts.append("</div>")
    if end and end[-1].get("wall_seconds") is not None:
        parts.append(f"<p>wall time: {end[-1]['wall_seconds']:.2f}s, "
                     f"{len(scores)} scored iterations</p>")

    # -- model: update:param ratios + histograms -------------------------
    if params:
        parts.append("<h2>Update : parameter ratios (log10)</h2>"
                     "<div class='row'>")
        names = sorted(params[-1]["params"])
        for name in names:
            pts = [(r["epoch"], r["params"][name]["update_ratio"])
                   for r in params if name in r["params"]
                   and "update_ratio" in r["params"][name]]
            if pts:
                parts.append(_svg_line(pts, w=320, h=120, color="#d62728",
                                       label=name, ylog=True))
        parts.append("</div><h2>Parameter histograms (last epoch)</h2>"
                     "<div class='row'>")
        last = params[-1]["params"]
        for name in names:
            ent = last[name]
            parts.append(_svg_hist(ent["hist"], ent["edges"], label=name))
        parts.append("</div><h2>Parameter stats (last epoch)</h2><table>"
                     "<tr><th>param</th><th>mean</th><th>std</th>"
                     "<th>norm</th><th>update norm</th></tr>")
        for name in names:
            ent = last[name]
            parts.append(
                f"<tr><td>{_html.escape(name)}</td>"
                f"<td>{ent['mean']:.4g}</td><td>{ent['std']:.4g}</td>"
                f"<td>{ent['norm']:.4g}</td>"
                f"<td>{ent.get('update_norm', float('nan')):.4g}</td></tr>")
        parts.append("</table>")

    # -- system: memory (monitor/memstats.py — docs/observability.md) ----
    if memory or memory_plans or oom_events:
        parts.append("<h2>Memory</h2>")
    if memory:
        # x = sample index, NOT iteration/epoch: records from different
        # producers (listener flushes carry iterations, StatsListener
        # epochs, serving samples neither) share one storage, and a
        # mixed axis would make the polyline double back on itself —
        # append order is time order, so the index is always monotonic
        parts.append("<div class='row'>")
        parts.append(_svg_line(
            [(i, r["bytes_in_use"] / 2**20)
             for i, r in enumerate(memory)],
            label="HBM in use (MiB) over samples", color="#9467bd"))
        parts.append(_svg_line(
            [(i, r["peak_bytes"] / 2**20)
             for i, r in enumerate(memory)],
            label="HBM peak (MiB) over samples", color="#8c564b"))
        if any(r.get("headroom") is not None for r in memory):
            parts.append(_svg_line(
                [(i, r["headroom"] / 2**20)
                 for i, r in enumerate(memory)
                 if r.get("headroom") is not None],
                label="HBM headroom (MiB) over samples", color="#2ca02c"))
        parts.append("</div>")
        # per-device watermark curves: a lopsided mesh shows one device
        # pinned at its limit while the fleet total looks healthy
        dev_names = sorted({d.get("device", "?") for r in memory
                            for d in r.get("devices", ())})
        if len(dev_names) > 1:
            parts.append("<div class='row'>")
            for name in dev_names[:16]:
                pts = []
                for i, r in enumerate(memory):
                    for d in r.get("devices", ()):
                        if d.get("device") == name:
                            pts.append((i,
                                        d.get("bytes_in_use", 0) / 2**20))
                if pts:
                    parts.append(_svg_line(
                        pts, w=320, h=120, color="#9467bd",
                        label=f"{name} in use (MiB)"))
            parts.append("</div>")
        last = memory[-1]
        tracked = last.get("tracked") or {}
        bits = [f"{len(memory)} samples"]
        if last.get("bytes_limit"):
            bits.append(f"limit {last['bytes_limit'] / 2**20:.0f} MiB")
        for tag, nb in sorted(tracked.items()):
            bits.append(f"{tag} {nb / 2**20:.1f} MiB "
                        f"({(last.get('tracked_counts') or {}).get(tag, 0)}"
                        f" transfers)")
        if last.get("live_skipped"):
            bits.append(f"{last['live_skipped']} live arrays unsized")
        parts.append("<p>" + ", ".join(bits) + "</p>")
    if memory_plans:
        # newest plan per program label (re-captures refresh)
        by_prog: dict = {}
        for r in memory_plans:
            by_prog[r.get("program", "?")] = r
        plans = [by_prog[k] for k in sorted(by_prog)]
        parts.append(_svg_budget(
            plans, label="compiled-program memory plans "
                         "(memory_analysis)"))
        parts.append(
            "<table><tr><th>program</th><th>steps</th><th>args MiB</th>"
            "<th>temps MiB</th><th>out MiB</th><th>total MiB</th>"
            "<th>GFLOPs/step</th></tr>")
        for p in plans:
            fps = p.get("flops_per_step")
            parts.append(
                f"<tr><td>{_html.escape(str(p.get('program', '?')))}</td>"
                f"<td>{p.get('steps', 1)}</td>"
                f"<td>{(p.get('argument_bytes', 0) or 0) / 2**20:.2f}</td>"
                f"<td>{(p.get('temp_bytes', 0) or 0) / 2**20:.2f}</td>"
                f"<td>{(p.get('output_bytes', 0) or 0) / 2**20:.2f}</td>"
                f"<td>{(p.get('total_bytes', 0) or 0) / 2**20:.2f}</td>"
                f"<td>{'—' if fps is None else format(fps / 1e9, '.3f')}"
                f"</td></tr>")
        parts.append("</table>")
    if oom_events:
        parts.append(
            f"<h3>OOM events ({len(oom_events)})</h3><table>"
            f"<tr><th>program</th><th>step</th><th>epoch</th>"
            f"<th>live arrays</th><th>live MiB</th><th>devices</th>"
            f"</tr>")
        for r in oom_events[-10:]:
            devs = "; ".join(
                f"{d.get('device', '?')}: "
                f"{(d.get('bytes_in_use', 0) or 0) / 2**20:.1f} MiB"
                + (f"/{d.get('bytes_limit', 0) / 2**20:.0f}"
                   if d.get("bytes_limit") else "")
                for d in (r.get("devices") or [])[:4]) or "—"
            lb = r.get("live_bytes")
            parts.append(
                f"<tr><td>{_html.escape(str(r.get('program', '?')))}</td>"
                f"<td>{r.get('step', '—')}</td>"
                f"<td>{r.get('epoch', '—')}</td>"
                f"<td>{r.get('live_arrays', '—')}</td>"
                f"<td>{'—' if lb is None else format(lb / 2**20, '.1f')}"
                f"</td><td>{_html.escape(devs)}</td></tr>")
        parts.append("</table><p>device memory exhausted — forensics "
                     "in the faults records (docs/observability.md "
                     "\"OOM forensics\")</p>")

    # -- layer health: in-graph tensorstats (monitor/tensorstats.py) -----
    if tensorstats:
        # bounded like the trace dump: a long monitored run holds tens
        # of thousands of samples, and /report renders this LIVE per
        # request — stride-downsample to a readable column budget
        # (always keeping the newest record, which feeds the table)
        ts_total = len(tensorstats)
        max_cols = 160
        if ts_total > max_cols:
            stride = -(-ts_total // max_cols)
            tensorstats = tensorstats[::-stride][::-1]
        layer_names = sorted({n for r in tensorstats
                              for n in r.get("layers", {})})
        parts.append("<h2>Layer health (device-side tensorstats)</h2>"
                     "<div class='row'>")
        # update:param ratio over time, one chart per layer (the
        # dead↔exploding spectrum LayerHealthWatcher polices)
        for name in layer_names:
            pts = [(r["iter"], r["layers"][name]["update_ratio"])
                   for r in tensorstats if name in r.get("layers", {})
                   and r["layers"][name].get("update_ratio") is not None]
            if pts:
                parts.append(_svg_line(
                    pts, w=320, h=120, color="#d62728",
                    label=f"{name} update:param (in-graph)", ylog=True))
        parts.append("</div>")
        # grad-norm heatmap: layers x sampled steps, log color scale
        # (None = poisoned/absent stats -> NaN -> grey cell)
        def _fnum(v):
            return float("nan") if v is None else float(v)

        matrix = [[_fnum(r["layers"].get(name, {}).get("grad_l2"))
                   for r in tensorstats] for name in layer_names]
        if any("grad_l2" in r["layers"].get(n, {}) for r in tensorstats
               for n in layer_names):
            parts.append(_svg_heatmap(
                matrix, layer_names,
                label="gradient L2 norm per layer over sampled steps"))
        last = tensorstats[-1]["layers"]
        parts.append(
            "<table><tr><th>layer</th><th>grad L2</th>"
            "<th>update:param</th><th>nonfinite</th><th>zeros</th>"
            "<th>|x| range (log2)</th></tr>")
        for name in layer_names:
            ent = last.get(name, {})
            nonf = sum(ent.get(f"{p}_nonfinite", 0)
                       for p in ("grad", "update", "param"))
            rng = "—"
            if ent.get("grad_hist"):
                lo = tensorstats[-1].get("hist_min_exp", 0)
                nz = [i for i, c in enumerate(ent["grad_hist"]) if c]
                if nz:
                    rng = f"[{lo + nz[0]}, {lo + nz[-1]}]"
            ur = ent.get("update_ratio")
            parts.append(
                f"<tr><td>{_html.escape(name)}</td>"
                f"<td>{_fnum(ent.get('grad_l2')):.4g}</td>"
                f"<td>{'—' if ur is None else format(ur, '.4g')}</td>"
                f"<td>{nonf}</td>"
                f"<td>{ent.get('grad_zeros', 0)}</td>"
                f"<td>{rng}</td></tr>")
        shown = "" if ts_total == len(tensorstats) \
            else f" ({len(tensorstats)} shown)"
        parts.append(
            f"</table><p>{ts_total} in-graph samples{shown} (every "
            f"{tensorstats[-1].get('every_n', '?')} steps) — gradients/"
            f"updates summarized inside the compiled step, fetched at "
            f"flush boundaries (docs/observability.md)</p>")

    # -- observability: step-time breakdown + span timeline --------------
    if steptime:
        parts.append("<h2>Step-time breakdown</h2>")
        parts.append(_svg_stack(
            steptime, label="wall time per flush (stacked by stage)"))
        tot = {k: sum(r.get(k, 0.0) for r in steptime)
               for k, _, _ in _STAGE_COLORS}
        wall = sum(tot.values()) or 1.0
        last = steptime[-1]
        parts.append(
            "<p>" + ", ".join(
                f"{k[:-2].replace('_', ' ')} {100 * v / wall:.1f}%"
                for k, v in tot.items())
            + f" — step ms p50 {last.get('step_ms_p50', 0):.3f} / "
              f"p95 {last.get('step_ms_p95', 0):.3f} over "
              f"{sum(r.get('steps', 0) for r in steptime)} steps</p>")
    if stragglers:
        parts.append(f"<h2>Stragglers ({len(stragglers)})</h2><table>"
                     "<tr><th>iteration</th><th>step (s)</th>"
                     "<th>EMA (s)</th><th>ratio</th></tr>")
        for r in stragglers[-20:]:
            parts.append(
                f"<tr><td>{r.get('iteration', '?')}</td>"
                f"<td>{r.get('step_s', 0):.4f}</td>"
                f"<td>{r.get('ema_s', 0):.4f}</td>"
                f"<td>{r.get('ratio', 0):.2f}x</td></tr>")
        parts.append("</table>")
    if traces:
        parts.append("<h2>Span timeline</h2>")
        parts.append(_svg_swimlane(traces[-1].get("spans", []),
                                   label="trace spans (tail)"))

    # -- compile latency: persistent-cache hit/miss accounting -----------
    if compiles:
        c = compiles[-1]
        misses = c.get("miss_compiles",
                       max(0, c.get("backend_compiles", 0)
                           - c.get("cache_hits", 0)))
        parts.append(
            f"<h2>Compilation</h2><p>{c.get('backend_compiles', 0)} XLA "
            f"compiles — {c.get('cache_hits', 0)} persistent-cache hits, "
            f"{misses} real (miss) compiles; "
            f"{c.get('backend_compile_seconds', 0.0):.2f}s in the "
            f"backend ({c.get('cache_load_seconds', 0.0):.2f}s of it "
            f"loading cache hits), "
            f"{c.get('trace_seconds', 0.0):.2f}s tracing, "
            f"{c.get('lower_seconds', 0.0):.2f}s lowering, "
            f"{c.get('build_seconds', 0.0):.2f}s building models, "
            f"servers and first fits around "
            f"{c.get('precompiles', 0)} programs built ahead of time, "
            f"{c.get('plan_analyze_seconds', 0.0):.2f}s reading their "
            f"memory plans, "
            f"{c.get('saved_seconds', 0.0):.2f}s saved by the cache "
            f"(compilecache/, docs/cold_start.md)</p>")

    # -- static analysis: pre-compile graph/config findings (analyze/) ---
    if analyses:
        a = analyses[-1]
        counts = a.get("counts") or {}
        g = a.get("graph") or {}
        sev_color = {"error": "#d62728", "warn": "#ff7f0e",
                     "info": "#888"}
        parts.append(
            f"<h2>Static analysis</h2><p>{a.get('context', '?')} "
            f"context — {g.get('ops', '?')} ops / "
            f"{g.get('vars', '?')} vars, {a.get('rules_run', '?')} "
            f"rules in {a.get('seconds', 0.0):.3f}s: "
            + ", ".join(f"{counts.get(s, 0)} {s}"
                        for s in ("error", "warn", "info"))
            + " (analyze/, docs/static_analysis.md)</p>")
        findings = a.get("findings") or []
        if findings:
            order = {"error": 0, "warn": 1, "info": 2}
            findings = sorted(findings,
                              key=lambda f: order.get(
                                  f.get("severity"), 3))
            parts.append("<table><tr><th>severity</th><th>rule</th>"
                         "<th>subject</th><th>finding</th></tr>")
            for f in findings[:50]:
                sev = str(f.get("severity", "?"))
                tip = " | ".join(
                    list(f.get("provenance") or [])
                    + ([f"fix: {f['fix_hint']}"]
                       if f.get("fix_hint") else []))
                parts.append(
                    f"<tr><td style='color:"
                    f"{sev_color.get(sev, '#222')}'>"
                    f"{_html.escape(sev)}</td>"
                    f"<td>{_html.escape(str(f.get('rule_id', '?')))}"
                    f"</td>"
                    f"<td>{_html.escape(str(f.get('subject', '?')))}"
                    f"</td>"
                    f"<td title='{_html.escape(tip)}'>"
                    f"{_html.escape(str(f.get('message', '')))}"
                    f"</td></tr>")
            parts.append("</table>")
            extra = a.get("truncated", 0) + max(0, len(findings) - 50)
            if extra:
                parts.append(f"<p>({extra} further findings elided)</p>")
        else:
            parts.append("<p>clean — no findings.</p>")

    # -- elasticity: resharded restores across topology changes ----------
    if reshards:
        parts.append(
            f"<h2>Elastic reshards ({len(reshards)})</h2><table>"
            f"<tr><th>step</th><th>shards</th><th>mesh</th>"
            f"<th>arrays</th><th>MiB gathered</th><th>seconds</th></tr>")
        for r in reshards[-20:]:
            fm = r.get("from_mesh")
            tm = r.get("to_mesh")
            mesh = (f"{fm} → {tm}" if fm or tm else "—")
            if r.get("from_shards") is not None or \
                    r.get("to_processes") is not None:
                shards = (f"{r.get('from_shards', '?')} → "
                          f"{r.get('to_processes', '?')}")
            else:
                # trainer-origin records (in-process mesh change, no
                # shard-count crossing) carry device counts instead
                shards = (f"{r.get('from_devices', '?')} → "
                          f"{r.get('to_devices', '?')} dev")
            parts.append(
                f"<tr><td>{r.get('step', '?')}</td>"
                f"<td>{_html.escape(shards)}</td>"
                f"<td>{_html.escape(str(mesh))}</td>"
                f"<td>{r.get('arrays', 0)}</td>"
                f"<td>{r.get('bytes', 0) / 2**20:.2f}</td>"
                f"<td>{r.get('seconds', 0.0):.4f}</td></tr>")
        parts.append("</table><p>save-on-N / restore-on-M elastic "
                     "restores (checkpoint/reshard.py, "
                     "docs/elastic_training.md)</p>")

    # -- data plane: streaming-pipeline telemetry (datapipe/) ------------
    if datapipe:
        parts.append("<h2>Data pipeline</h2><div class='row'>")
        pts = [(i, r["records_per_sec"]) for i, r in enumerate(datapipe)
               if r.get("records_per_sec") is not None]
        if pts:
            parts.append(_svg_line(
                pts, label="records/sec over flushes", color="#17becf"))
        wait_pts = [(i, 100.0 * r["data_wait_frac"])
                    for i, r in enumerate(datapipe)
                    if r.get("data_wait_frac") is not None]
        if wait_pts:
            parts.append(_svg_line(
                wait_pts, label="data-wait % of wall per flush",
                color="#d62728"))
        parts.append("</div>")
        tot = {k: sum(r.get(k, 0) for r in datapipe)
               for k in ("records", "batches", "read_retries",
                         "rows_quarantined", "records_withheld",
                         "worker_restarts", "requeues", "slow_reads")}
        last = datapipe[-1]
        bits = [f"{tot['records']} records / {tot['batches']} batches "
                f"delivered",
                f"{last.get('passes_started', '?')} passes"]
        for key, label in (("read_retries", "read retries"),
                           ("rows_quarantined", "rows quarantined"),
                           ("records_withheld", "records withheld"),
                           ("worker_restarts", "worker restarts"),
                           ("requeues", "requeues"),
                           ("slow_reads", "slow reads")):
            if tot[key]:
                bits.append(f"{tot[key]} {label}")
        if last.get("quarantined_shards"):
            bits.append(f"{last['quarantined_shards']} shards "
                        f"quarantined")
        parts.append("<p>" + ", ".join(bits) +
                     " (datapipe/, docs/data_pipeline.md)</p>")
        util = last.get("worker_utilization") or {}
        if util:
            parts.append("<table><tr><th>prefetch worker</th>"
                         "<th>utilization (last flush)</th></tr>")
            for w in sorted(util):
                parts.append(f"<tr><td>{_html.escape(str(w))}</td>"
                             f"<td>{100.0 * util[w]:.1f}%</td></tr>")
            parts.append("</table>")

    # -- integrity: stalls, scrub cycles, quarantined rot ----------------
    if integrity or stall_events:
        parts.append("<h2>Integrity</h2>")
    if stall_events:
        parts.append(
            f"<h3>Stalls ({len(stall_events)})</h3><table>"
            f"<tr><th>boundary</th><th>blocked (s)</th>"
            f"<th>deadline (s)</th><th>threads dumped</th></tr>")
        for r in stall_events[-20:]:
            parts.append(
                f"<tr><td>{_html.escape(str(r.get('boundary', '?')))}"
                f"</td><td>{r.get('waited_s', 0.0):.3f}</td>"
                f"<td>{r.get('deadline_s', 0.0):.3f}</td>"
                f"<td>{r.get('threads', '—')}</td></tr>")
        parts.append("</table><p>adaptive-deadline expiries "
                     "(integrity/watchdog.py — forensics in the "
                     "integrity records / GET /stacks)</p>")
    if integrity:
        scrubs = [r for r in integrity if r.get("event") == "scrub"]
        rot = [r for r in integrity
               if r.get("event") in ("checkpoint_quarantined",
                                     "checkpoint_rotten")]
        if scrubs:
            tot_dirs = sum(r.get("scanned", 0) for r in scrubs)
            tot_bytes = sum(r.get("bytes", 0) for r in scrubs)
            tot_rot = sum(r.get("rotten", 0) for r in scrubs)
            parts.append(
                f"<p>checkpoint scrubber: {len(scrubs)} cycle(s), "
                f"{tot_dirs} step dir(s) re-hashed "
                f"({tot_bytes / 2**20:.1f} MiB), {tot_rot} rotten "
                f"(checkpoint/scrub.py)</p>")
        if rot:
            parts.append(
                "<table><tr><th>rotten step</th><th>problems</th>"
                "<th>quarantined to</th></tr>")
            for r in rot[-20:]:
                probs = "; ".join(str(p) for p in
                                  (r.get("problems") or [])[:3])
                dest = str(r.get("quarantined_to") or "—")
                parts.append(
                    f"<tr><td>{r.get('step', '?')}</td>"
                    f"<td>{_html.escape(probs)}</td>"
                    f"<td>{_html.escape(dest)}</td></tr>")
            parts.append("</table>")
        probes = [r for r in integrity
                  if r.get("event") == "stall_forensics"]
        if probes:
            parts.append(f"<p>{len(probes)} stall forensics record(s) "
                         f"captured (all-thread stacks + HBM snapshot "
                         f"+ active plan)</p>")

    # -- serving: traffic + the resilience rail --------------------------
    if serving:
        s = serving[-1]
        c = s.get("counters", {})
        parts.append(
            f"<h2>Serving</h2><p>{c.get('requests_served', 0)} served / "
            f"{c.get('requests_submitted', 0)} submitted — "
            f"{c.get('requests_rejected', 0)} rejected (queue full), "
            f"{c.get('requests_shed', 0)} shed (SLO admission/breaker), "
            f"{c.get('requests_timed_out', 0)} timed out, "
            f"{c.get('requests_failed', 0)} failed; "
            f"{c.get('batches_dispatched', 0)} batches, "
            f"{c.get('compiles', 0)} compiled shapes "
            f"({c.get('warmup_compiles', 0)} prewarmed)</p>")
        gen = s.get("generative") or {}
        if gen:
            parts.append(
                f"<p>generative: {gen.get('tokens_generated', 0)} tokens "
                f"({gen.get('tokens_per_sec', 0.0)} tok/s lifetime), "
                f"{gen.get('prefills', 0)} prefills, "
                f"{gen.get('decode_steps', 0)} decode steps, slot "
                f"occupancy {gen.get('slot_occupancy', 0.0):.1%} of "
                f"{gen.get('max_slots', 0)} slots "
                f"(docs/serving.md \"Generative serving\")</p>")
        if gen.get("spec_rounds"):
            parts.append(
                f"<p>speculative: {gen.get('draft_accepted', 0)}/"
                f"{gen.get('draft_tokens', 0)} draft tokens accepted "
                f"({gen.get('draft_acceptance_rate', 0.0):.1%}) over "
                f"{gen.get('spec_rounds', 0)} rounds, "
                f"{gen.get('draft_rejected', 0)} rejected "
                f"(docs/serving.md \"Decode speed\")</p>")
        paged = s.get("paged") or {}
        if paged:
            parts.append(
                f"<p>paged KV: {paged.get('num_blocks', 0)} blocks x "
                f"{paged.get('block_size', 0)} tokens, pool occupancy "
                f"{paged.get('pool_occupancy', 0.0):.1%}, prefix hit "
                f"rate {paged.get('prefix_hit_rate', 0.0):.1%} "
                f"({paged.get('prefix_blocks_hit', 0)} blocks reused), "
                f"{paged.get('blocks_per_request', 0.0)} blocks/request, "
                f"{paged.get('evictions', 0)} cache evictions "
                f"(docs/serving.md \"Paged KV &amp; prefix caching\")</p>")
        lat = s.get("latency_ms", {})
        if lat:
            parts.append("<table><tr><th>lane</th><th>count</th>"
                         "<th>mean</th><th>p50</th><th>p95</th>"
                         "<th>p99</th><th>max (ms)</th></tr>")
            for lane in ("queue_wait", "e2e", "exec", "ttft",
                         "intertoken", "prefill"):
                v = lat.get(lane)
                if v is None:
                    continue
                low = " ⚠" if v.get("low_sample") and \
                    v.get("count") else ""
                parts.append(
                    f"<tr><td>{lane}</td><td>{v.get('count', 0)}{low}"
                    f"</td>"
                    + "".join(f"<td>{v.get(k, 0.0):.3f}</td>"
                              for k in ("mean", "p50", "p95", "p99",
                                        "max"))
                    + "</tr>")
            parts.append("</table>")
        res = s.get("resilience") or {}
        resil_bits = [f"{k.replace('_', ' ')} {c[k]}" for k in
                      ("requests_shed", "breaker_opens", "worker_restarts",
                       "requests_requeued", "poisoned_quarantined",
                       "bisect_splits", "exec_faults", "reloads",
                       "reload_rollbacks") if c.get(k)]
        if res or resil_bits:
            lead = (f"breaker <b>{res.get('breaker_state', '?')}</b>"
                    if res.get("breaker_state") else "")
            reload_note = ""
            if res.get("last_reload_step") is not None:
                reload_note = (
                    f"; last hot reload: step {res['last_reload_step']}"
                    + (" (rolled back)" if res.get("last_reload_failed")
                       else ""))
            parts.append(
                "<p>resilience: " + "; ".join(
                    b for b in ([lead] if lead else []) + resil_bits)
                + reload_note + " (docs/serving.md \"Resilience\")</p>")
    if serving_faults:
        parts.append(
            f"<h3>Serving fault-rail events ({len(serving_faults)})"
            f"</h3><table><tr><th>event</th><th>cause</th>"
            f"<th>detail</th></tr>")
        for r in serving_faults[-20:]:
            detail = {k: v for k, v in r.items()
                      if k not in ("type", "event", "cause", "t",
                                   "origin") and v is not None}
            parts.append(
                f"<tr><td>{_html.escape(str(r.get('event', '?')))}</td>"
                f"<td>{_html.escape(str(r.get('cause', '—')))}</td>"
                f"<td>{_html.escape(str(detail) if detail else '—')}"
                f"</td></tr>")
        parts.append("</table>")

    # -- serving fleet: routing / retries / deploys / autoscale ----------
    if fleet:
        rec = fleet[-1]
        c = rec.get("counters", {})
        agg = rec.get("fleet", {})
        parts.append(f"<h2>Fleet ({agg.get('n_ready', 0)}/"
                     f"{agg.get('n_replicas', 0)} replicas ready)</h2>")
        routing_bits = [
            f"routed {c.get('requests_routed', 0)}",
            f"affinity {c.get('routed_affinity', 0)}",
            f"spill {c.get('routed_spill', 0)}",
            f"least-loaded {c.get('routed_least_loaded', 0)}",
            f"affinity hit rate "
            f"<b>{agg.get('affinity_hit_rate', 0.0):.1%}</b>"]
        parts.append("<p>routing: " + "; ".join(routing_bits) + "</p>")
        retry_bits = [f"{k.replace('_', ' ')} {c[k]}" for k in
                      ("retries", "sheds_seen", "replica_deaths_seen",
                       "retry_giveups", "requests_failed",
                       "requests_timed_out") if c.get(k)]
        if retry_bits:
            parts.append("<p>resilience: " + "; ".join(retry_bits)
                         + f" ({c.get('requests_ok', 0)} ok)</p>")
        ops_bits = [f"{k.replace('_', ' ')} {c[k]}" for k in
                    ("deploys", "deploy_rollbacks", "scale_up_events",
                     "scale_down_events") if c.get(k)]
        if ops_bits:
            parts.append("<p>operations: " + "; ".join(ops_bits)
                         + "</p>")
        dur = rec.get("durability")
        if dur:
            fs = dur.get("journal_fsync_ms") or {}
            parts.append(
                f"<p>durability: <b>{dur.get('resumes', 0)}</b> resumes"
                f" salvaging <b>{dur.get('tokens_salvaged', 0)}</b> "
                f"tokens; {dur.get('dedup_drops', 0)} duplicate "
                f"deliveries absorbed; "
                f"{dur.get('recovered_requests', 0)} journal replays; "
                f"{dur.get('journal_records', 0)} journal records "
                f"(fsync p99 {fs.get('p99', 0.0):.2f} ms)</p>")
        slo = rec.get("slo")
        if slo:
            parts.append("<h3>SLO</h3>")
            objectives = slo.get("objectives") or {}
            head = []
            for field in sorted(objectives):
                o = objectives[field]
                head.append(
                    f"{_html.escape(field)} ≤ {o.get('target_ms', 0):g} "
                    f"ms: attainment <b>{o.get('attainment', 1.0):.2%}"
                    f"</b>, burn rate <b>{o.get('burn_rate', 0.0):.2f}×"
                    f"</b>, p50 {o.get('p50_ms', 0.0):.1f} / p99 "
                    f"{o.get('p99_ms', 0.0):.1f} ms")
            outcomes = slo.get("outcomes") or {}
            oc = "; ".join(f"{k} {v}" for k, v in sorted(outcomes.items())
                           if v)
            parts.append(
                "<p>" + "; ".join(head)
                + f" (window {slo.get('window', 0)} of "
                f"{slo.get('total', 0)} total"
                + (f"; outcomes: {oc}" if oc else "") + ")</p>")
            # attainment over time: one point per published fleet record
            for field in sorted(objectives):
                pts = []
                for i, frec in enumerate(fleet):
                    o = ((frec.get("slo") or {}).get("objectives")
                         or {}).get(field)
                    if o is not None:
                        pts.append((float(i), float(
                            o.get("attainment", 1.0))))
                if len(pts) > 1:
                    parts.append(_svg_line(
                        pts, color="#2ca02c",
                        label=f"SLO attainment ({field})"))
            worst = slo.get("worst_traces") or []
            if worst:
                parts.append(
                    "<p>worst sampled traces (TTFT breakdown — "
                    "where the time went):</p>"
                    "<table><tr><th>trace</th><th>ttft ms</th>"
                    "<th>queue wait</th><th>prefill</th>"
                    "<th>first decode</th><th>e2e ms</th>"
                    "<th>replica</th><th>retries</th><th>kept</th>"
                    "</tr>")
                for e in worst:
                    bd = e.get("breakdown") or {}
                    ttft = e.get("ttft_ms")
                    e2e = e.get("e2e_ms")
                    parts.append(
                        f"<tr><td>{_html.escape(str(e.get('trace_id')))}"
                        f"</td>"
                        f"<td>{0.0 if ttft is None else ttft:.1f}</td>"
                        f"<td>{bd.get('queue_wait_ms', 0.0):.1f}</td>"
                        f"<td>{bd.get('prefill_ms', 0.0):.1f}</td>"
                        f"<td>{bd.get('first_decode_ms', 0.0):.1f}</td>"
                        f"<td>{0.0 if e2e is None else e2e:.1f}</td>"
                        f"<td>{_html.escape(str(e.get('replica') or '—'))}"
                        f"</td><td>{e.get('retries', 0)}</td>"
                        f"<td>{_html.escape(str(e.get('kept') or '—'))}"
                        f"</td></tr>")
                parts.append("</table>")
            parts.append("<p>(docs/observability.md \"Request tracing "
                         "&amp; SLOs\")</p>")
        replicas = rec.get("replicas", {})
        if replicas:
            parts.append(
                "<table><tr><th>replica</th><th>ready</th>"
                "<th>queue</th><th>occupancy</th>"
                "<th>p99 step ms</th><th>routed</th></tr>")
            for name in sorted(replicas):
                rep = replicas[name]
                parts.append(
                    f"<tr><td>{_html.escape(str(name))}</td>"
                    f"<td>{'yes' if rep.get('ready') else 'NO'}</td>"
                    f"<td>{rep.get('queue_depth', 0)}</td>"
                    f"<td>{rep.get('occupancy', 0.0):.0%}</td>"
                    f"<td>{rep.get('p99_decode_step_ms', 0.0):.2f}</td>"
                    f"<td>{rep.get('routed', 0)}</td></tr>")
            parts.append("</table>")
        parts.append("<p>(docs/serving.md \"Fleet\")</p>")

    # -- observability: unified metrics snapshot -------------------------
    if metrics:
        flat = metrics[-1].get("metrics", {})
        parts.append(f"<h2>Metrics (last snapshot, {len(flat)} series)"
                     f"</h2><table><tr><th>metric</th><th>value</th>"
                     f"</tr>")
        for name in sorted(flat):
            v = flat[name]
            vs = f"{v:.6g}" if isinstance(v, float) else str(v)
            parts.append(f"<tr><td>{_html.escape(str(name))}</td>"
                         f"<td>{_html.escape(vs)}</td></tr>")
        parts.append("</table>")

    # -- forward compatibility: record types this renderer predates ------
    unknown: dict = {}
    for r in storage.records:
        t = r.get("type")
        if t not in _KNOWN_TYPES:
            key = str(t)
            unknown[key] = unknown.get(key, 0) + 1
    if unknown:
        listing = ", ".join(f"{_html.escape(k)} ({n})"
                            for k, n in sorted(unknown.items()))
        parts.append(
            f"<p style='color:#888;border-top:1px solid #ddd;"
            f"padding-top:6px'>unrendered record types: {listing} — "
            f"this report predates them; the records are intact in the "
            f"storage</p>")

    parts.append("</body></html>")
    return "\n".join(parts)


def write_report(storage: StatsStorage, path: str,
                 title: str = "Training report") -> str:
    html = render_report(storage, title)
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(html)
    return path
