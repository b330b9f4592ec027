#!/usr/bin/env python
"""Summarize a Chrome trace-event JSON emitted by `core/telemetry.py`.

Usage:
    python scripts/trace_report.py TRACE.json [--top N] [--json]

Reads the `{"traceEvents": [...]}` file a `Tracer.to_chrome_trace()`
produced (e.g. any `simulate_drain` / `MeshMakespan.timeline()` run
under `telemetry.use(...)`) and prints:

  * **per-link utilization** — busy seconds per physical-link track on
    the virtual clock, as a fraction of the trace's virtual end;
  * **per-request wait/wire/stall split** — each drained request's
    queue-wait, dependency-stall, wire, and latency seconds;
  * **top-N serialization offenders** — the requests that spent longest
    blocked behind unrelated queue items (the queue-wait column, which
    is exactly the time a priority scheduler could reclaim);
  * **control-plane summary** — span/instant counts per name (selector
    choices, compiles + cache hits, retries).

`--json` emits the same summary as one JSON object.
Stdlib-only; no repro import needed to read a trace.
"""
from __future__ import annotations

import argparse
import json
import sys

#: telemetry.py's pid assignment (see CONTROL_PID / VIRTUAL_PID there)
CONTROL_PID = 1
VIRTUAL_PID = 2
US = 1e6   # virtual-clock events are exported as priced-seconds * 1e6


def load_events(path: str) -> list:
    with open(path) as fh:
        doc = json.load(fh)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    if not isinstance(events, list):
        raise SystemExit(f"{path}: not a Chrome trace-event file")
    return events


def track_names(events: list) -> dict:
    """(pid, tid) -> track name, from the "M" thread_name metadata."""
    names = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            names[(ev["pid"], ev["tid"])] = ev["args"]["name"]
    return names


def summarize(events: list, top: int = 10) -> dict:
    names = track_names(events)
    end_us = 0.0
    links: dict = {}      # track -> busy_us
    requests: list = []
    control: dict = {}    # "span:<name>" / "instant:<name>" -> count
    for ev in events:
        ph = ev.get("ph")
        if ph == "M":
            continue
        pid = ev.get("pid")
        track = names.get((pid, ev.get("tid")), "?")
        if pid == VIRTUAL_PID:
            if ph == "X":
                t1 = float(ev["ts"]) + float(ev.get("dur", 0.0))
                end_us = max(end_us, t1)
                if track.startswith("link:"):
                    links[track] = links.get(track, 0.0) \
                        + float(ev.get("dur", 0.0))
                elif ev.get("name") == "request":
                    a = ev.get("args", {})
                    requests.append({
                        "rids": a.get("rids", []),
                        "track": track,
                        "start_s": float(ev["ts"]) / US,
                        "end_s": t1 / US,
                        "queue_wait_s": a.get("queue_wait_s"),
                        "dep_stall_s": a.get("dep_stall_s"),
                        "wire_s": a.get("wire_s"),
                        "lat_s": a.get("lat_s"),
                        "retries": a.get("retries"),
                        "backoff_s": a.get("backoff_s"),
                        "status": a.get("status"),
                    })
        elif pid == CONTROL_PID:
            kind = {"X": "span", "i": "instant"}.get(ph)
            if kind is not None:
                key = f"{kind}:{ev.get('name')}"
                control[key] = control.get(key, 0) + 1
    end_s = end_us / US
    link_util = {
        t: {"busy_s": busy / US,
            "utilization": (busy / end_us) if end_us > 0 else 0.0}
        for t, busy in sorted(links.items())
    }
    offenders = sorted(
        (r for r in requests if r.get("queue_wait_s") is not None),
        key=lambda r: r["queue_wait_s"], reverse=True)[:top]
    return {
        "virtual_end_s": end_s,
        "links": link_util,
        "requests": requests,
        "offenders": offenders,
        "control": dict(sorted(control.items())),
    }


def _fmt_s(v) -> str:
    return "-" if v is None else f"{v:.3e}"


def print_report(rep: dict, stream=sys.stdout) -> None:
    w = stream.write
    w(f"virtual clock end: {rep['virtual_end_s']:.6e} s\n\n")
    if rep["links"]:
        w("per-link utilization (virtual clock):\n")
        for track, d in rep["links"].items():
            w(f"  {track:<28} busy {d['busy_s']:.3e} s"
              f"  util {d['utilization']:6.1%}\n")
        w("\n")
    if rep["requests"]:
        w("per-request split (queue-wait / dep-stall / wire / alpha):\n")
        for r in rep["requests"]:
            rids = "+".join(str(i) for i in r["rids"]) or "?"
            w(f"  rid {rids:<8} {r['track']:<16}"
              f" wait {_fmt_s(r['queue_wait_s'])}"
              f" stall {_fmt_s(r['dep_stall_s'])}"
              f" wire {_fmt_s(r['wire_s'])}"
              f" alpha {_fmt_s(r['lat_s'])}"
              f"  {r['status'] or ''}\n")
        w("\n")
    if rep["offenders"]:
        w("top serialization offenders (by queue-wait):\n")
        for r in rep["offenders"]:
            rids = "+".join(str(i) for i in r["rids"]) or "?"
            w(f"  rid {rids:<8} {r['track']:<16}"
              f" waited {_fmt_s(r['queue_wait_s'])} s\n")
        w("\n")
    if rep["control"]:
        w("control plane:\n")
        for key, n in rep["control"].items():
            w(f"  {key:<40} x{n}\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Summarize a telemetry Chrome trace")
    ap.add_argument("trace", help="trace JSON (Tracer.to_chrome_trace())")
    ap.add_argument("--top", type=int, default=10,
                    help="serialization offenders to list (default 10)")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of text")
    args = ap.parse_args(argv)
    rep = summarize(load_events(args.trace), top=args.top)
    if args.json:
        json.dump(rep, sys.stdout, indent=2)
        sys.stdout.write("\n")
    else:
        print_report(rep)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
