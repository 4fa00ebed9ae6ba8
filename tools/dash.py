#!/usr/bin/env python3
"""Live terminal dashboard over a serving node's telemetry surface.

Renders the three observability endpoints the SLO stack exposes —
`GET /series` (windowed time series), `GET /slo` (burn rates + firing
objectives + anomaly warnings), `GET /healthz` (degraded verdict with
reasons) — as unicode sparklines and tables, entirely from the stdlib:

  python tools/dash.py --url http://127.0.0.1:8080            one shot
  python tools/dash.py --url ... --watch 2                    refresh loop
  python tools/dash.py --url ... --prefix serving_latency     filter keys
  python tools/dash.py --url ... --html dash.html             single-file
                                                              HTML (inline
                                                              SVG, no JS)
"""

from __future__ import annotations

import argparse
import html as _html
import json
import sys
import time
import urllib.request

_BARS = "▁▂▃▄▅▆▇█"


# --------------------------------------------------------------- fetch
def _fetch(url: str, timeout: float = 10.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _fetch_all(base: str):
    """(series, slo, healthz) — each None if its endpoint is absent."""
    out = []
    for path in ("/series", "/slo", "/healthz"):
        try:
            out.append(_fetch(base + path))
        except Exception:
            out.append(None)
    return tuple(out)


# ----------------------------------------------------------- sparkline
def _resample(vals, width):
    """Bucket-mean a value list down (or repeat it up) to `width`."""
    if not vals:
        return []
    if len(vals) <= width:
        return list(vals)
    out = []
    for i in range(width):
        lo = i * len(vals) // width
        hi = max(lo + 1, (i + 1) * len(vals) // width)
        chunk = vals[lo:hi]
        out.append(sum(chunk) / len(chunk))
    return out


def spark(vals, width: int = 40) -> str:
    """Unicode sparkline; flat series render as a mid-level bar."""
    vals = _resample([float(v) for v in vals], width)
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _BARS[3] * len(vals)
    span = hi - lo
    return "".join(_BARS[min(7, int((v - lo) / span * 7.999))]
                   for v in vals)


def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.4g}"
    return str(v)


# ----------------------------------------------------- terminal render
def render_terminal(series, slo, healthz, *, prefix: str = "",
                    width: int = 40, max_series: int = 40) -> str:
    lines = []
    if healthz:
        status = healthz.get("status", "?")
        lines.append(f"health: {status}")
        for r in healthz.get("reasons") or []:
            lines.append(f"  ! {r}")
    if slo and slo.get("enabled", True) and slo.get("slos"):
        lines.append("")
        lines.append(f"{'slo':24} {'value':>10} {'burn fast':>10} "
                     f"{'burn slow':>10}  state")
        for s in slo["slos"]:
            state = "FIRING" if s.get("firing") else "ok"
            if s.get("firing") and s.get("since"):
                state += f" (since {time.strftime('%H:%M:%S', time.localtime(s['since']))})"
            lines.append(f"{s['name'][:24]:24} {_fmt(s.get('value')):>10} "
                         f"{_fmt(s.get('burn_fast')):>10} "
                         f"{_fmt(s.get('burn_slow')):>10}  {state}")
        for w in slo.get("anomalies") or []:
            lines.append(f"  anomaly[{w.get('kind')}]: {w.get('message')}")
    if series and series.get("series"):
        lines.append("")
        keys = [k for k in sorted(series["series"])
                if k.startswith(prefix)] if prefix else \
            sorted(series["series"])
        shown = keys[:max_series]
        klen = min(44, max((len(k) for k in shown), default=8))
        for key in shown:
            s = series["series"][key]
            vals = [p[1] for p in s.get("points") or []]
            if not vals:
                continue
            lines.append(f"{key[:klen]:{klen}} {spark(vals, width)} "
                         f"{_fmt(vals[-1])}")
        if len(keys) > max_series:
            lines.append(f"  … {len(keys) - max_series} more series "
                         f"(narrow with --prefix)")
    elif series is not None and not (series or {}).get("series"):
        lines.append("")
        lines.append("no series yet (is the sampler enabled? "
                     "InferenceServer(..., slo=True))")
    return "\n".join(lines) + "\n"


# ---------------------------------------------------------- html render
def _svg_series(key, pts, *, w=520, h=64):
    """One inline-SVG polyline panel for a series."""
    vals = [p[1] for p in pts]
    lo, hi = min(vals), max(vals)
    span = (hi - lo) or 1.0
    n = max(len(vals) - 1, 1)
    coords = " ".join(
        f"{i / n * (w - 8) + 4:.1f},"
        f"{h - 16 - (v - lo) / span * (h - 24):.1f}"
        for i, v in enumerate(vals))
    return (
        f'<div class="panel"><div class="k">{_html.escape(key)} '
        f'<span class="v">{_fmt(vals[-1])}</span></div>'
        f'<svg width="{w}" height="{h}" viewBox="0 0 {w} {h}">'
        f'<polyline fill="none" stroke="#4c9" stroke-width="1.5" '
        f'points="{coords}"/>'
        f'<text x="4" y="{h - 3}" class="t">min {_fmt(lo)}</text>'
        f'<text x="{w - 4}" y="{h - 3}" text-anchor="end" class="t">'
        f'max {_fmt(hi)}</text></svg></div>')


def render_html(series, slo, healthz, *, prefix: str = "",
                refresh_s: int = 0) -> str:
    status = (healthz or {}).get("status", "unknown")
    color = {"ok": "#4c9", "degraded": "#e66"}.get(status, "#999")
    head = [
        "<!doctype html><html><head><meta charset='utf-8'>",
        "<title>dl4j-tpu dashboard</title>",
    ]
    if refresh_s:
        head.append(f"<meta http-equiv='refresh' content='{refresh_s}'>")
    head.append(
        "<style>body{background:#111;color:#ddd;font:13px/1.5 monospace;"
        "margin:16px}h1{font-size:16px}.badge{display:inline-block;"
        "padding:2px 10px;border-radius:10px;background:" + color +
        ";color:#111;font-weight:bold}.panel{display:inline-block;"
        "margin:6px;padding:6px;background:#1a1a1a;border:1px solid #333;"
        "border-radius:4px}.k{margin-bottom:2px}.v{color:#4c9}"
        ".t{fill:#666;font-size:10px}table{border-collapse:collapse;"
        "margin:8px 0}td,th{border:1px solid #333;padding:3px 10px;"
        "text-align:right}th{color:#999}td:first-child,th:first-child"
        "{text-align:left}.firing{color:#e66;font-weight:bold}"
        ".reason{color:#e66}</style></head><body>")
    body = [f"<h1>dl4j-tpu telemetry "
            f"<span class='badge'>{_html.escape(status)}</span></h1>"]
    for r in (healthz or {}).get("reasons") or []:
        body.append(f"<div class='reason'>! {_html.escape(r)}</div>")
    if slo and slo.get("slos"):
        body.append("<table><tr><th>slo</th><th>value</th>"
                    "<th>burn fast</th><th>burn slow</th>"
                    "<th>state</th></tr>")
        for s in slo["slos"]:
            state = ("<span class='firing'>FIRING</span>"
                     if s.get("firing") else "ok")
            body.append(
                f"<tr><td>{_html.escape(s['name'])}</td>"
                f"<td>{_fmt(s.get('value'))}</td>"
                f"<td>{_fmt(s.get('burn_fast'))}</td>"
                f"<td>{_fmt(s.get('burn_slow'))}</td>"
                f"<td>{state}</td></tr>")
        body.append("</table>")
        for w in slo.get("anomalies") or []:
            body.append(f"<div class='reason'>anomaly[{_html.escape(str(w.get('kind')))}]: "
                        f"{_html.escape(str(w.get('message')))}</div>")
    for key in sorted((series or {}).get("series") or {}):
        if prefix and not key.startswith(prefix):
            continue
        pts = series["series"][key].get("points") or []
        if pts:
            body.append(_svg_series(key, pts))
    body.append("</body></html>")
    return "".join(head) + "".join(body)


# ----------------------------------------------------------------- cli
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--url", default="http://127.0.0.1:8080",
                    help="serving node base URL")
    ap.add_argument("--prefix", default="",
                    help="only show series whose key starts with this")
    ap.add_argument("--width", type=int, default=40,
                    help="sparkline width in characters")
    ap.add_argument("--watch", type=float, default=0.0, metavar="SECS",
                    help="re-render every SECS seconds until ^C")
    ap.add_argument("--html", metavar="FILE",
                    help="write a single-file HTML dashboard and exit")
    ap.add_argument("--refresh", type=int, default=0,
                    help="auto-refresh interval baked into the HTML")
    args = ap.parse_args(argv)

    base = args.url.rstrip("/")
    series, slo, healthz = _fetch_all(base)
    if series is None and slo is None and healthz is None:
        print(f"no telemetry endpoints reachable at {base}",
              file=sys.stderr)
        return 2

    if args.html:
        with open(args.html, "w") as f:
            f.write(render_html(series, slo, healthz, prefix=args.prefix,
                                refresh_s=args.refresh))
        print(f"wrote {args.html}")
        return 0

    try:
        while True:
            out = render_terminal(series, slo, healthz,
                                  prefix=args.prefix, width=args.width)
            if args.watch:
                sys.stdout.write("\x1b[2J\x1b[H")   # clear + home
            sys.stdout.write(out)
            sys.stdout.flush()
            if not args.watch:
                return 0
            time.sleep(args.watch)
            series, slo, healthz = _fetch_all(base)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
