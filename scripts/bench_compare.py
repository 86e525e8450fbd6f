#!/usr/bin/env python3
"""Bench regression gate: a fresh bench JSON vs the committed
BENCH_r*.json trajectory.

Every growth round commits its bench result as ``BENCH_rNN.json``
(``{"n", "cmd", "rc", "tail", "parsed"}`` where ``parsed`` is the one
JSON line bench.py printed). Nothing READ that trajectory: a
regression only surfaced when a human eyeballed two files. This gate
closes the loop. (The committed set is empty since PR 21 removed the
records taken before PR 1 on another attach of the chip — a chip run
of today's code must not be held against them; with no history for a
mode the gate passes as a baseline.)

    python bench.py > /tmp/fresh.json
    python scripts/bench_compare.py /tmp/fresh.json

classifies the fresh result's mode from its metric/unit (each
BENCH_MODE prints a distinctive headline), finds the committed
trajectory entries of the SAME mode, and applies that mode's named
threshold against the latest committed value. Non-zero exit on
regression, so CI can gate on it.

Named thresholds (direction-aware — a faster chaos MTTR is an
improvement, a faster tok/s headline is a regression):

  ws / engine / fleet / overload / roofline   tok/s, higher is better,
                                              regression below -5%
  multiturn / radix / chaos                   ms, lower is better,
                                              regression above +25%
  disagg                                      ITL p99 gain ratio,
                                              higher is better, below
                                              -25% (tail-latency
                                              derived, latency band)
  longctx / int4 / paged                      capacity ratios, higher
                                              is better, below -10%
  structured                                  overhead frac, must stay
                                              < 0.05 absolute
  profiler                                    on/off delta frac, must
                                              stay within |0.01|

Latency and ratio modes get looser bands than throughput: the
committed trajectory shows tok/s is stable run to run while TTFT-class
medians on a shared box swing tens of percent.

A fresh mode with no committed history PASSES with a note — the first
recording of a new mode is a baseline, not a regression. ``--smoke``
self-tests the gate against the committed trajectory (the latest entry
must pass against its own history; a synthetically halved one must
fail) without running any bench.

Stdlib only; no engine import, so it runs anywhere instantly.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (mode, matcher(metric, unit), kind, threshold). First match wins.
# kind: "higher" — regression if value < latest * (1 - tol);
#       "lower"  — regression if value > latest * (1 + tol);
#       "abs"    — regression if |value| > tol (no history needed).
_MODES: tuple[tuple, ...] = (
    ("profiler",
     lambda m, u: m.startswith("continuous-profiler"), "abs", 0.01),
    ("structured",
     lambda m, u: m.startswith("structured"), "abs", 0.05),
    ("chaos", lambda m, u: m.startswith("chaos"), "lower", 0.25),
    ("multiturn",
     lambda m, u: m.startswith("multiturn"), "lower", 0.25),
    ("radix", lambda m, u: m.startswith("radix"), "lower", 0.25),
    # Decode ITL p99 gain ratio (role-split over mixed): higher is
    # better, and it is tail-latency derived so it gets the loose
    # latency-class band, not the throughput one.
    ("disagg", lambda m, u: m.startswith("disagg"), "higher", 0.25),
    ("longctx", lambda m, u: m.startswith("longctx"), "higher", 0.10),
    ("int4", lambda m, u: m.startswith("int4"), "higher", 0.10),
    ("paged", lambda m, u: m.startswith("paged"), "higher", 0.10),
    ("fleet", lambda m, u: m.startswith("fleet"), "higher", 0.05),
    ("overload", lambda m, u: m.startswith("overload"), "higher", 0.05),
    ("roofline", lambda m, u: m.startswith("roofline"), "higher", 0.05),
    # The default ws/engine headline: "WebSocket output tok/s, ..." /
    # "engine-seam output tok/s, ...". Last so the specific modes
    # above (also tok/s) never fall through to it.
    ("ws", lambda m, u: u == "tok/s" and "output tok/s" in m,
     "higher", 0.05),
)


def classify(parsed: dict) -> tuple[str, str, float] | None:
    """(mode, kind, threshold) for a bench headline, or None."""
    metric = str(parsed.get("metric", ""))
    unit = str(parsed.get("unit", ""))
    for mode, match, kind, tol in _MODES:
        if match(metric, unit):
            return mode, kind, tol
    return None


def load_parsed(path: str) -> dict:
    """The bench headline dict from either shape: a raw bench stdout
    JSON line ({"metric", "value", ...}) or a committed BENCH_r*.json
    wrapper ({"parsed": {...}}). '-' reads stdin."""
    if path == "-":
        raw = sys.stdin.read()
    else:
        with open(path) as f:
            raw = f.read()
    # Committed records are one pretty-printed document; bench stdout
    # captures may carry log noise around the headline line. Try the
    # whole document first, then the last line that parses as a JSON
    # object, same as the bench drivers do.
    d = None
    try:
        d = json.loads(raw)
    except json.JSONDecodeError:
        pass
    if not isinstance(d, dict):
        d = None
    for line in [] if d is not None else \
            reversed(raw.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                d = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    if d is None:
        raise SystemExit(f"bench_compare: no JSON object in {path}")
    if "parsed" in d and isinstance(d["parsed"], dict):
        d = d["parsed"]
    if "value" not in d:
        raise SystemExit(
            f"bench_compare: {path} has no 'value' field — not a "
            f"bench headline")
    return d


def load_history(pattern: str) -> list[tuple[str, dict]]:
    """[(filename, parsed)] for every committed bench record, oldest
    first (BENCH_r01 < BENCH_r02 < ... by name)."""
    out = []
    for p in sorted(glob.glob(pattern)):
        try:
            with open(p) as f:
                d = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        parsed = d.get("parsed")
        if isinstance(parsed, dict) and "value" in parsed:
            out.append((os.path.basename(p), parsed))
    return out


def compare(fresh: dict, history: list[tuple[str, dict]],
            out=sys.stdout) -> int:
    """Print the verdict; return a process exit code (0 pass,
    1 regression, 2 unclassifiable)."""
    cls = classify(fresh)
    if cls is None:
        print(f"FAIL unclassifiable headline: metric="
              f"{fresh.get('metric')!r} unit={fresh.get('unit')!r}",
              file=out)
        return 2
    mode, kind, tol = cls
    value = float(fresh["value"])

    same = [(name, p) for name, p in history
            if (classify(p) or ("", "", 0.0))[0] == mode]
    traj = " -> ".join(f"{p['value']:g}" for _, p in same) or "(none)"
    print(f"mode={mode} fresh={value:g} {fresh.get('unit', '')} "
          f"trajectory: {traj}", file=out)

    if kind == "abs":
        # Contract bound, not a trajectory diff: these headlines are
        # overhead fractions whose acceptance bar is absolute.
        if abs(value) > tol:
            print(f"FAIL {mode}: |{value:g}| exceeds the {tol:g} "
                  f"absolute bound", file=out)
            return 1
        print(f"PASS {mode}: |{value:g}| within the {tol:g} absolute "
              f"bound", file=out)
        return 0

    if not same:
        print(f"PASS {mode}: no committed history — fresh value "
              f"recorded as the baseline", file=out)
        return 0

    ref_name, ref = same[-1]
    ref_v = float(ref["value"])
    if kind == "higher":
        floor = ref_v * (1.0 - tol)
        if value < floor:
            print(f"FAIL {mode}: {value:g} is "
                  f"{(1 - value / ref_v):.1%} below {ref_name} "
                  f"({ref_v:g}); threshold {tol:.0%}", file=out)
            return 1
        print(f"PASS {mode}: {value:g} vs {ref_name} {ref_v:g} "
              f"(floor {floor:g}, threshold -{tol:.0%})", file=out)
        return 0
    # kind == "lower"
    ceil = ref_v * (1.0 + tol)
    if value > ceil:
        print(f"FAIL {mode}: {value:g} is "
              f"{(value / ref_v - 1):.1%} above {ref_name} "
              f"({ref_v:g}); threshold {tol:.0%}", file=out)
        return 1
    print(f"PASS {mode}: {value:g} vs {ref_name} {ref_v:g} "
          f"(ceiling {ceil:g}, threshold +{tol:.0%})", file=out)
    return 0


def smoke(pattern: str) -> int:
    """Self-test against the committed trajectory: the newest entry
    must pass vs its own history, a halved copy must fail, and the two
    absolute-bound modes must gate both directions."""
    history = load_history(pattern)
    if not history:
        # No committed records: the smoke tests the GATE, so a made-up
        # two-entry trajectory serves (no device produced these values).
        history = [(f"synthetic_r0{i}",
                    {"metric": "WebSocket output tok/s, synthetic",
                     "value": v, "unit": "tok/s"})
                   for i, v in ((1, 1000.0), (2, 1010.0))]
    latest = dict(history[-1][1])
    rc = compare(latest, history)
    if rc != 0:
        print("SMOKE FAIL: latest committed entry flagged against "
              "its own history", file=sys.stderr)
        return 1
    bad = dict(latest)
    bad["value"] = float(latest["value"]) * 0.5
    if compare(bad, history) != 1:
        print("SMOKE FAIL: a 50% throughput drop was not flagged",
              file=sys.stderr)
        return 1
    prof_ok = {"metric": "continuous-profiler overhead delta frac, x",
               "value": -0.004, "unit": "frac"}
    prof_bad = dict(prof_ok, value=0.03)
    if compare(prof_ok, history) != 0 or compare(prof_bad, history) != 1:
        print("SMOKE FAIL: profiler absolute bound misgated",
              file=sys.stderr)
        return 1
    mttr_ok = {"metric": "chaos engine-restart MTTR-to-first-token "
                         "p50 ms, x", "value": 100.0, "unit": "ms"}
    if compare(mttr_ok, history) != 0:
        print("SMOKE FAIL: chaos entry without history did not pass "
              "as a new baseline", file=sys.stderr)
        return 1
    print("SMOKE PASS: gate flags drops, honours absolute bounds, "
          "and records new modes as baselines")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("fresh", nargs="?",
                    help="fresh bench JSON (bench.py stdout or a "
                         "BENCH_r*.json; '-' for stdin)")
    ap.add_argument("--history",
                    default=os.path.join(REPO, "BENCH_r*.json"),
                    help="glob of committed bench records "
                         "(default: repo root BENCH_r*.json)")
    ap.add_argument("--smoke", action="store_true",
                    help="self-test the gate against the committed "
                         "trajectory; runs no bench")
    args = ap.parse_args()
    if args.smoke:
        return smoke(args.history)
    if not args.fresh:
        ap.error("fresh bench JSON path required (or --smoke)")
    return compare(load_parsed(args.fresh), load_history(args.history))


if __name__ == "__main__":
    sys.exit(main())
