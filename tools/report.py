#!/usr/bin/env python
"""Replay a telemetry JSONL log into a human-readable run report.

``launch/train.py --log-dir DIR`` (and ``launch/serve.py``, the examples,
``benchmarks/common.write_rows``) all emit one JSONL stream of schema'd
rows (``repro.telemetry.sink.ROW_KINDS``).  This tool is the read side:
it reconstructs, post-hoc and offline,

  * the PBT **family tree** — every evolve row carries ``parents[i]`` =
    the member whose state slot ``i`` now holds, so the full clone
    genealogy of the final population is recoverable;
  * per-member **hyper trajectories** (the time series of ``members``
    rows);
  * per-phase **wall-clock** (iterate / update / evolve / eval / ckpt)
    totals and per-iteration means;
  * **compile events** counted by attribution label (warmup / steady /
    resize / promotion) — recompiles in steady state are a bug report;
  * **serving latency** windows (p50/p99, batch fill, queue depth) and
    the promotion audit trail.

    python tools/report.py /tmp/run/telemetry.jsonl
    python tools/report.py /tmp/run              # dir: finds telemetry.jsonl
    python tools/report.py LOG --check           # schema-validate only (CI)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.telemetry.sink import validate_row  # noqa: E402


# --------------------------------------------------------------- loading
def load_rows(path) -> list[dict]:
    """All rows of a telemetry JSONL file (a directory means its
    ``telemetry.jsonl``), in write order."""
    p = Path(path)
    if p.is_dir():
        p = p / "telemetry.jsonl"
    rows = []
    with open(p) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError as e:
                raise ValueError(f"{p}:{i}: not valid JSON: {e}") from None
    return rows


def check_rows(rows) -> list[str]:
    """Schema errors ('' when valid) — one entry per offending row."""
    errors = []
    for i, row in enumerate(rows, 1):
        err = validate_row(row)
        if err is not None:
            errors.append(f"row {i}: {err}")
    return errors


def by_kind(rows, kind: str) -> list[dict]:
    return [r for r in rows if r.get("kind") == kind]


# --------------------------------------------------------------- lineage
def lineage_tree(rows):
    """Reconstruct the PBT family tree from ``evolve`` rows.

    Nodes are ``(slot, birth_step)`` — a member slot gets a new node
    whenever it receives a new state (step 0 init, or an evolve that
    copies another member / draws fresh).  Returns ``(roots, children,
    current)``: root nodes, a node -> child-nodes map (insertion order),
    and ``current[slot]`` = the live node of each final slot.
    """
    evolves = by_kind(rows, "evolve")
    n = max((len(e["parents"]) for e in evolves), default=0)
    if not n:
        for m in by_kind(rows, "members"):
            for key in ("fitness", "hypers"):
                v = m.get(key)
                if isinstance(v, dict):
                    v = next(iter(v.values()), [])
                if isinstance(v, list):
                    n = max(n, len(v))
    roots = [(i, 0) for i in range(n)]
    children: dict = {node: [] for node in roots}
    current = dict(enumerate(roots))
    for e in evolves:
        step, parents = e["step"], e["parents"]
        prev = dict(current)
        for i, p in enumerate(parents):
            p = int(p)
            if p == i:
                continue                       # survivor: same state line
            node = (i, step)
            children[node] = []
            if p < 0 or p not in prev:
                roots.append(node)             # fresh draw: a new founder
            else:
                children[prev[p]].append(node)
            current[i] = node
    return roots, children, current


def render_tree(roots, children, current, fitness=None) -> list[str]:
    """ASCII family tree; live slots are starred with their final
    fitness."""
    live = {node: slot for slot, node in current.items()}
    lines = []

    def label(node):
        slot, step = node
        s = f"m{slot}@{step}"
        if node in live:
            s += " *"
            if fitness is not None and live[node] < len(fitness):
                s += f" fit={fitness[live[node]]:+.2f}"
        return s

    def walk(node, prefix, tail):
        branch = "" if not prefix and tail is None else \
            ("└─ " if tail else "├─ ")
        lines.append(prefix + branch + label(node))
        kids = children.get(node, [])
        ext = "" if tail is None else ("   " if tail else "│  ")
        for k, kid in enumerate(kids):
            walk(kid, prefix + ext, k == len(kids) - 1)

    for root in roots:
        walk(root, "", None)
    return lines


# ------------------------------------------------------------ summaries
def hyper_trajectories(rows):
    """``{hyper: [(step, [per-member values]), ...]}`` from members
    rows."""
    out: dict[str, list] = {}
    for m in by_kind(rows, "members"):
        for name, vals in (m.get("hypers") or {}).items():
            out.setdefault(name, []).append((m["step"], vals))
    return out


def fitness_series(rows):
    """``[(step, [per-member fitness]), ...]`` from members rows."""
    return [(m["step"], m["fitness"]) for m in by_kind(rows, "members")
            if m.get("fitness") is not None]


def _timer_summary(rows, field):
    out: dict[str, dict] = {}
    for it in by_kind(rows, "iter"):
        for name, secs in (it.get(field) or {}).items():
            d = out.setdefault(name, {"secs": 0.0, "iters": 0})
            d["secs"] += secs
            d["iters"] += 1
    for d in out.values():
        d["secs"] = round(d["secs"], 4)
        d["ms_per_iter"] = round(1e3 * d["secs"] / max(1, d["iters"]), 3)
    return out


def phase_summary(rows):
    """``{phase: {"secs": total, "iters": n, "ms_per_iter": mean,
    "cpu_secs": total, "blocked_secs": total}}`` over the iter rows'
    ``phases`` (host DISPATCH wall time per phase) and ``phases_cpu`` (the
    thread's CPU time in it; blocked is wall less CPU, the time the host
    waited).  The CPU and blocked totals are None for a log whose rows
    carry no ``phases_cpu``."""
    out = _timer_summary(rows, "phases")
    cpu = _timer_summary(rows, "phases_cpu")
    for name, d in out.items():
        c = cpu.get(name)
        d["cpu_secs"] = None if c is None else c["secs"]
        d["blocked_secs"] = None if c is None \
            else round(d["secs"] - c["secs"], 4)
    return out


def block_summary(rows):
    """Same aggregation over the iter rows' optional ``blocks`` (host WAIT
    time, ``RunTelemetry.block``).  dispatch ≪ block ≈ wall means the run
    was serial; a small block next to real device work means the wait was
    hidden under enqueued-ahead work (the overlapped engine's signature)."""
    return _timer_summary(rows, "blocks")


def compile_summary(rows):
    """``{label: {"count": n, "secs": total}}`` over compile rows."""
    out: dict[str, dict] = {}
    for c in by_kind(rows, "compile"):
        d = out.setdefault(c["label"], {"count": 0, "secs": 0.0})
        d["count"] += 1
        d["secs"] += c["secs"]
    for d in out.values():
        d["secs"] = round(d["secs"], 4)
    return out


def serve_summary(rows):
    """Aggregate of serve rows: request-weighted latency and fill."""
    serves = by_kind(rows, "serve")
    if not serves:
        return None
    total = sum(s.get("requests", s["count"]) for s in serves)
    return {
        "windows": len(serves),
        "requests": total,
        "p50_ms": round(max(s["p50_ms"] for s in serves), 3),
        "p99_ms": round(max(s["p99_ms"] for s in serves), 3),
        "fill": round(sum(s.get("fill", 1.0) for s in serves)
                      / len(serves), 3),
    }


# ---------------------------------------------------------------- report
def _fmt_members(vals, width: int = 8):
    if not isinstance(vals, list):
        return str(vals)
    return "[" + " ".join(f"{v:+.3g}" if isinstance(v, (int, float))
                          else str(v) for v in vals) + "]"


def report(rows, out=None) -> None:
    # late-bind stdout: a default of ``sys.stdout`` freezes whatever stream
    # is installed at import time (pytest capture, redirects)
    w = (sys.stdout if out is None else out).write
    for run in by_kind(rows, "run"):
        meta = " ".join(f"{k}={v}" for k, v in (run.get("meta") or
                                                {}).items())
        w(f"run {run['run_id']}  jax={run.get('jax')} "
          f"devices={run.get('devices')} ({run.get('platform')})  "
          f"{meta}\n")
    for eng in by_kind(rows, "engine"):
        w("engine: " + " ".join(
            f"{k}={v}" for k, v in eng.items()
            if k not in ("kind", "t")) + "\n")

    phases = phase_summary(rows)
    if phases:
        iters = by_kind(rows, "iter")
        w(f"\nphases ({len(iters)} iterations; host dispatch time: wall "
          f"= cpu + blocked)\n")
        for name, d in sorted(phases.items(), key=lambda kv:
                              -kv[1]["secs"]):
            split = "" if d["cpu_secs"] is None else \
                f"  cpu {d['cpu_secs']:>8.3f}s  blocked " \
                f"{d['blocked_secs']:>8.3f}s"
            w(f"  {name:<10} {d['secs']:>9.3f}s total  "
              f"{d['ms_per_iter']:>9.3f} ms/iter{split}  "
              f"({d['iters']} iters)\n")

    blocks = block_summary(rows)
    if blocks:
        w("blocks (block-until-ready wait time; serial: block ≈ wall — "
          "overlapped: collect hides under the update block)\n")
        for name, d in sorted(blocks.items(), key=lambda kv:
                              -kv[1]["secs"]):
            w(f"  {name:<10} {d['secs']:>9.3f}s total  "
              f"{d['ms_per_iter']:>9.3f} ms/iter  ({d['iters']} iters)\n")

    compiles = compile_summary(rows)
    if compiles:
        total = sum(d["count"] for d in compiles.values())
        secs = sum(d["secs"] for d in compiles.values())
        w(f"\ncompiles ({total} events, {secs:.2f}s)\n")
        for label, d in sorted(compiles.items(),
                               key=lambda kv: -kv[1]["secs"]):
            w(f"  {label:<10} {d['count']:>4} x  {d['secs']:>8.3f}s\n")
        steady = compiles.get("steady", {}).get("count", 0)
        if steady:
            w(f"  NOTE: {steady} steady-state recompile(s) — the fused "
              f"call's shapes should be stable after warmup\n")

    ckpts = by_kind(rows, "ckpt")
    if ckpts:
        w(f"\ncheckpoints: {len(ckpts)} saves, "
          f"{sum(c['secs'] for c in ckpts):.3f}s dispatch\n")

    fitness = fitness_series(rows)
    hypers = hyper_trajectories(rows)
    if fitness or hypers:
        w("\npopulation\n")
    for step, vals in fitness:
        w(f"  fitness @{step:<6} {_fmt_members(vals)}\n")
    for name, series in hypers.items():
        w(f"  hyper {name}\n")
        for step, vals in series:
            w(f"    @{step:<6} {_fmt_members(vals)}\n")

    evolves = by_kind(rows, "evolve")
    if evolves:
        w(f"\nlineage ({len(evolves)} evolve events)\n")
        for e in evolves:
            moves = [f"{i}<-{p}" for i, p in enumerate(e["parents"])
                     if int(p) != i]
            w(f"  @{e['step']:<6} {' '.join(moves) if moves else '(no-op)'}"
              + (f"  [{e['strategy']}]" if e.get("strategy") else "")
              + "\n")
        final = fitness[-1][1] if fitness else None
        roots, children, current = lineage_tree(rows)
        w("  family tree (m<slot>@<birth step>; * = in final "
          "population)\n")
        for line in render_tree(roots, children, current, final):
            w("    " + line + "\n")

    srv = serve_summary(rows)
    if srv:
        w(f"\nserving: {srv['requests']} requests over "
          f"{srv['windows']} windows  p50<= {srv['p50_ms']} ms  "
          f"p99<= {srv['p99_ms']} ms  fill {srv['fill']}\n")
    promos = by_kind(rows, "promotion")
    if promos:
        w(f"promotions ({len(promos)})\n")
        for p in promos:
            w(f"  @{p['step']:<6} members={p['members']} "
              f"+{p.get('promoted')} -{p.get('demoted')}\n")

    benches = by_kind(rows, "bench")
    if benches:
        w(f"\nbenchmark rows ({len(benches)})\n")
        for b in benches:
            w("  " + " ".join(f"{k}={v}" for k, v in b.items()
                              if k not in ("kind", "t")) + "\n")

    for end in by_kind(rows, "run_end"):
        w("\nrun_end: " + " ".join(
            f"{k}={v}" for k, v in end.items()
            if k not in ("kind", "t")) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="reconstruct a run report from a telemetry JSONL log")
    ap.add_argument("log", help="telemetry.jsonl (or a --log-dir that "
                    "contains one)")
    ap.add_argument("--check", action="store_true",
                    help="schema-validate every row and exit (CI mode: "
                    "exit 1 on any invalid row)")
    args = ap.parse_args(argv)

    rows = load_rows(args.log)
    errors = check_rows(rows)
    if args.check:
        for e in errors:
            print(e, file=sys.stderr)
        kinds = sorted({r.get("kind") for r in rows})
        print(f"{args.log}: {len(rows)} rows, kinds={kinds}: "
              + ("INVALID" if errors else "OK"))
        return 1 if errors else 0
    if errors:
        print(f"warning: {len(errors)} schema-invalid row(s); "
              f"run --check for details", file=sys.stderr)
    report(rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
