#!/usr/bin/env python
"""Perf-regression attribution: diff two perf artifacts and rank what
changed, attributed through the existing ledgers.

Two ``run_report.json`` files or two Watchtower TSDB dumps
(``TimeSeriesStore.save()``) are diffable (as is any pair of JSON
files) because everything reduces to numeric leaves under dotted keys.
The output is a ranked "what changed" table, each row attributed to the
ledger family its key belongs to (goodput buckets, comm bytes, compile
counts, step-ms percentiles, kv/adapter pool pressure, ...)::

    python scripts/perf_diff.py old_report.json new_report.json --top 15

Stdlib-only, host-only.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Dict, List, Optional, Tuple

# Attribution: first matching pattern names the ledger family a key
# belongs to.  Order matters — e.g. `compile` outranks the `_ms` latency
# catch-all so `compile_ms` lands in compiles.
CATEGORIES: Tuple[Tuple[str, str], ...] = (
    ("goodput", r"goodput|wall_clock|productive|overhead_fraction"),
    ("compiles", r"compil"),
    ("comm", r"comm_|_bytes|bandwidth|allreduce|allgather|reduce_scatter"),
    ("kv/pools", r"kv_|pages|adapter|pool|evict|spill"),
    ("slo/alerts", r"slo|burn|attainment|alert"),
    ("latency", r"ttft|tpot|e2e|queue_wait|_ms\b|_ms[._]|latency|p50|p9\d"),
    ("throughput", r"per_sec|per_token|throughput|mfu|samples|tokens"),
    ("resilience", r"straggler|desync|rollback|preempt|reshape|skipped"),
    ("timings", r"seconds|elapsed|duration|_s\b"),
)

# Keys that are wall-time stamps or identifiers, not perf signals.
_IGNORE_RE = re.compile(
    r"(^|\.)(written_at|measured|recorded_at|rotated_at|ts|t|time"
    r"|unixtime|version|seed|pid|port)($|\.)"
)


def categorize(key: str) -> str:
    low = key.lower()
    for name, pat in CATEGORIES:
        if re.search(pat, low):
            return name
    return "other"


def flatten(obj, prefix: str = "", out: Optional[Dict[str, float]] = None,
            ) -> Dict[str, float]:
    """Numeric leaves of any nested JSON value under dotted keys.  Lists
    of dicts index by a `name`/`model`/`leg`-like field when one exists
    (stable across runs) and by position otherwise."""
    if out is None:
        out = {}
    if isinstance(obj, bool):
        out[prefix] = float(obj)
    elif isinstance(obj, (int, float)):
        out[prefix] = float(obj)
    elif isinstance(obj, dict):
        for k, v in obj.items():
            flatten(v, f"{prefix}.{k}" if prefix else str(k), out)
    elif isinstance(obj, list):
        for i, v in enumerate(obj):
            tag = str(i)
            if isinstance(v, dict):
                for id_key in ("name", "model", "leg", "fn", "rule"):
                    if isinstance(v.get(id_key), str):
                        tag = v[id_key]
                        break
            flatten(v, f"{prefix}[{tag}]" if prefix else f"[{tag}]", out)
    return out


def _is_tsdb_dump(payload) -> bool:
    return (
        isinstance(payload, dict)
        and isinstance(payload.get("series"), list)
        and all(
            isinstance(s, dict) and "points" in s and "name" in s
            for s in payload["series"]
        )
    )


def _flatten_tsdb(payload: dict) -> Dict[str, float]:
    """A Watchtower dump reduces to one leaf per series — its LAST
    sample (the state the run ended in) — keyed by the exposition-style
    series key, so two dumps diff like two scrapes."""
    out: Dict[str, float] = {}
    for s in payload["series"]:
        labels = s.get("labels") or {}
        key = s["name"]
        if labels:
            inner = ",".join(
                f"{k}={v}" for k, v in sorted(labels.items())
            )
            key = f"{s['name']}{{{inner}}}"
        pts = s.get("points") or []
        if pts:
            out[key] = float(pts[-1][1])
    return out


def load_leaves(path: str) -> Dict[str, float]:
    with open(path, encoding="utf-8") as fp:
        payload = json.load(fp)
    if _is_tsdb_dump(payload):
        return _flatten_tsdb(payload)
    return flatten(payload)


def diff_leaves(old: Dict[str, float], new: Dict[str, float],
                min_pct: float = 0.5) -> List[dict]:
    """Ranked change rows: every key present in both sides whose value
    moved at least ``min_pct`` percent (or appeared/vanished), sorted by
    relative magnitude — the "what changed" table."""
    rows: List[dict] = []
    for key in sorted(set(old) | set(new)):
        if _IGNORE_RE.search(key):
            continue
        a, b = old.get(key), new.get(key)
        if a is None or b is None:
            rows.append({
                "key": key, "category": categorize(key),
                "old": a, "new": b, "delta": None,
                "pct": float("inf"),
                "note": "appeared" if a is None else "vanished",
            })
            continue
        if a == b:
            continue
        delta = b - a
        pct = abs(delta) / abs(a) * 100.0 if a else float("inf")
        if pct < min_pct:
            continue
        rows.append({
            "key": key, "category": categorize(key),
            "old": a, "new": b, "delta": delta, "pct": pct, "note": "",
        })
    rows.sort(key=lambda r: (-r["pct"], r["key"]))
    return rows


def diff_files(old_path: str, new_path: str,
               min_pct: float = 0.5) -> List[dict]:
    return diff_leaves(
        load_leaves(old_path), load_leaves(new_path), min_pct=min_pct
    )


def _fmt(v: Optional[float]) -> str:
    if v is None:
        return "-"
    if v != v:  # NaN
        return "nan"
    if abs(v) >= 1e6 or (v and abs(v) < 1e-3):
        return f"{v:.3g}"
    return f"{v:.4g}"


def format_table(rows: List[dict], top: int = 20) -> str:
    """The ranked attribution table plus a per-ledger rollup."""
    if not rows:
        return "no numeric leaves changed"
    shown = rows[:top]
    headers = ("category", "key", "old", "new", "delta", "pct")
    table = [
        (
            r["category"], r["key"], _fmt(r["old"]), _fmt(r["new"]),
            _fmt(r["delta"]) if r["delta"] is not None else r["note"],
            "new" if r["pct"] == float("inf") else f"{r['pct']:+.1f}%"
            if r["delta"] is not None and r["delta"] > 0
            else ("" if r["pct"] == float("inf") else f"-{r['pct']:.1f}%"),
        )
        for r in shown
    ]
    widths = [
        max(len(headers[i]), *(len(t[i]) for t in table))
        for i in range(len(headers))
    ]
    lines = [
        "  ".join(h.ljust(w) for h, w in zip(headers, widths)),
        "  ".join("-" * w for w in widths),
    ]
    lines += [
        "  ".join(c.ljust(w) for c, w in zip(t, widths)) for t in table
    ]
    by_cat: Dict[str, int] = {}
    for r in rows:
        by_cat[r["category"]] = by_cat.get(r["category"], 0) + 1
    rollup = ", ".join(
        f"{c}: {n}" for c, n in
        sorted(by_cat.items(), key=lambda kv: -kv[1])
    )
    lines.append("")
    lines.append(
        f"{len(rows)} changed leaves ({rollup})"
        + (f"; top {top} shown" if len(rows) > top else "")
    )
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    ap = argparse.ArgumentParser(
        description="diff two perf artifacts and attribute what changed",
    )
    ap.add_argument("old")
    ap.add_argument("new")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--min-pct", type=float, default=0.5,
                    help="hide leaves that moved less than this percent")
    ap.add_argument("--json", action="store_true",
                    help="emit the raw rows as JSON instead of the table")
    args = ap.parse_args(argv)
    rows = diff_files(args.old, args.new, min_pct=args.min_pct)
    if args.json:
        print(json.dumps(rows, indent=1, default=str))
    else:
        print(f"perf diff: {args.old} -> {args.new}")
        print(format_table(rows, top=args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
