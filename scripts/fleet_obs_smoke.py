#!/usr/bin/env python
"""Fleet observability-plane smoke — the
PR 19 tentpole end to end, with REAL OS processes (serving/fleet.py +
the router's fleet plane in serving/router.py):

1. **Metrics federation** — a 3-process fleet (1 prefill + 2 decode)
   serves a seeded open-loop trace byte-identical to in-driver
   ``generate()`` with zero post-warmup compiles per worker process,
   WHILE the router scrapes every worker's ``/metrics`` and re-exports
   the union on its own ``/metrics``: every worker series carries
   ``replica=``/``role=``/``generation=`` labels, each worker's
   ``compile_events_post_warmup_total`` is present (at 0), a re-scrape
   is byte-identical on the worker sections (no histogram
   double-count), the aggregated ``/healthz`` names each replica's
   post-warmup compile count and degradation level, and every loadgen
   row names the replica that served it.
2. **Cross-process tracing** — ``Router.save_fleet_trace`` merges
   ``GET /trace`` from every worker into ONE clock-aligned Perfetto
   timeline: >= 2 process lanes, and a migrated request whose
   prefill-side fragment (prefill worker's lane) ends before its
   decode-side span (a DIFFERENT pid's lane) begins.
3. **Incident bundles** — a real ``SIGKILL`` of a decode worker: the
   router's poller notices the death and assembles an
   ``incident_<ts>/`` bundle containing the router's own flight dump,
   every SURVIVING replica's flight dump, the federated metrics
   snapshot, SLO timelines, and the dead worker's stderr tail; the
   scrape-error counter for the dead replica ticks instead of the
   poller crashing.

Prints ``FLEET_OBS_SMOKE OK`` / ``FLEET_OBS_SMOKE FAIL: <why>``;
non-zero exit on any violation.  CPU-only, 3 worker processes, tiny
model.
"""

import json
import os
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def fail(msg: str) -> int:
    print(f"FLEET_OBS_SMOKE FAIL: {msg}")
    return 1


def worker_lines(text: str):
    """Federated sample lines carrying a replica= label (the worker
    sections; router-own series have none)."""
    return [
        ln for ln in text.splitlines()
        if ln and not ln.startswith("#") and 'replica="' in ln
    ]


def main() -> int:
    import jax

    from ml_trainer_tpu.generate import generate
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving.fleet import Fleet
    from ml_trainer_tpu.serving.loadgen import (
        ScheduledRequest, run_open_loop, schedule_from_trace,
        schedule_to_records,
    )

    model = get_model("gpt2_tiny", max_len=64)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    rng = np.random.default_rng(0)
    rows = [
        ScheduledRequest(
            arrival_s=i * 0.02, tenant=f"tenant{i % 2}",
            prompt=rng.integers(
                0, model.vocab_size, int(rng.integers(8, 25))
            ).astype(np.int32),
            max_new_tokens=8,
        )
        for i in range(8)
    ]
    trace = schedule_from_trace(schedule_to_records(rows))
    refs = [
        [int(t) for t in np.asarray(
            generate(model, variables, s.prompt[None], s.max_new_tokens)
        )[0]]
        for s in trace
    ]

    fleet = Fleet(
        roles=["prefill", "decode", "decode"],
        model_name="gpt2_tiny", max_len=64, max_batch=2,
        kv_page_size=8, prefill_chunk=16, seed=0,
    )
    fleet.start()
    incident_root = tempfile.mkdtemp(prefix="fleet-obs-smoke-")
    router = fleet.make_router(
        hedging=False, metrics_scrape_interval=0.1,
        incident_dir=incident_root, incident_min_interval_s=0.0,
    )
    workers = sorted(fleet.replicas)
    try:
        host, port = router.serve_http(port=0)
        url = f"http://{host}:{port}"

        # -- leg 1: federation under live traffic ----------------------
        for _ in range(2):  # untimed: workers compile to steady state
            run_open_loop(trace, url=url, time_scale=0.0)

        def compiles():
            return {
                n: int(r._get("/v1/spec")["compiles"] or 0)
                for n, r in fleet.replicas.items()
            }

        before = compiles()
        client = run_open_loop(trace, url=url, collect_tokens=True)
        after = compiles()
        if client["n_errors"]:
            return fail(f"{client['n_errors']} client error(s)")
        for r, ref in zip(client["per_request"], refs):
            if r.get("output") != ref:
                return fail(
                    "fleet output diverged from generate() with the "
                    "observability plane enabled"
                )
        fresh = {n: after[n] - before[n] for n in after}
        if any(fresh.values()):
            return fail(f"post-warmup worker recompiles: {fresh}")
        no_replica = [
            i for i, r in enumerate(client["per_request"])
            if not r.get("replica")
        ]
        if no_replica:
            return fail(f"loadgen rows missing replica id: {no_replica}")

        router.scrape_metrics(force=True)
        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as resp:
            fed = resp.read().decode()
        lines = worker_lines(fed)
        for name in workers:
            rep = fleet.replicas[name]
            want = (
                f'replica="{name}"', f'role="{rep.role}"', 'generation="'
            )
            if not any(
                ln.startswith("compile_events_post_warmup_total{")
                and all(w in ln for w in want)
                for ln in lines
            ):
                return fail(
                    f"federated exposition missing {name}'s labelled "
                    "compile_events_post_warmup_total"
                )
        router.scrape_metrics(force=True)
        if worker_lines(router.federated_metrics_text()) != lines:
            return fail(
                "re-scrape changed the federated worker sections "
                "(snapshots must replace, never accumulate)"
            )
        with urllib.request.urlopen(f"{url}/healthz", timeout=10) as resp:
            hz = json.loads(resp.read())
        for name in workers:
            h = hz.get("replicas", {}).get(name, {})
            for key in ("compile_events_post_warmup_total",
                        "degradation_level"):
                if key not in h:
                    return fail(
                        f"aggregated /healthz [{name}] missing {key}"
                    )
        print(
            f"# fleet obs smoke: {len(trace)} requests byte-identical "
            f"across 3 processes with the plane on, {len(lines)} "
            "federated worker lines, idempotent re-scrape, replica ids "
            "on every loadgen row"
        )

        # -- leg 2: one clock-aligned fleet trace ----------------------
        trace_path = os.path.join(incident_root, "fleet_trace.json")
        router.save_fleet_trace(trace_path)
        with open(trace_path, encoding="utf-8") as fp:
            merged = json.load(fp)
        events = merged.get("traceEvents", [])
        lanes = {e.get("pid") for e in events if e.get("ph") != "M"}
        if len(lanes) < 2:
            return fail(f"merged trace holds {len(lanes)} lane(s)")
        causal = None
        router_pid = os.getpid()  # the router's lane: its own request
        for ev in events:         # spans start at submit, pre-prefill
            name = ev.get("name", "")
            if not name.startswith("kv_wire "):
                continue
            tid = name.split(" ", 1)[1]
            pre = next(
                (e for e in events
                 if e.get("name") == f"request {tid} (prefill)"), None,
            )
            dec = next(
                (e for e in events
                 if e.get("name") == f"request {tid}"
                 and e.get("pid") not in (
                     (pre or {}).get("pid"), router_pid,
                 )), None,
            )
            if pre is None or dec is None:
                continue
            # Epoch alignment is exact on one host; allow the NTP
            # fallback's rtt/2 error bound.
            if dec["ts"] >= pre["ts"] + pre.get("dur", 0.0) - 5_000.0:
                causal = (tid, pre["pid"], dec["pid"])
                break
        if causal is None:
            return fail(
                "no migrated request spans two process lanes in causal "
                "order on the merged timeline"
            )
        print(
            f"# fleet obs smoke: merged trace {len(events)} events / "
            f"{len(lanes)} lanes, request {causal[0]} prefill@pid "
            f"{causal[1]} -> decode@pid {causal[2]} in causal order"
        )

        # -- leg 3: SIGKILL -> incident bundle -------------------------
        victim = fleet.replicas["decode0"]
        fleet.kill("decode0")  # SIGKILL, no goodbye
        deadline = time.monotonic() + 90
        bundle = None
        while time.monotonic() < deadline:
            bundle = router.last_incident_path
            if bundle and os.path.exists(
                os.path.join(bundle, "manifest.json")
            ):
                break
            time.sleep(0.1)
        else:
            return fail(
                "router never assembled an incident bundle after the "
                "SIGKILL"
            )
        have = set(os.listdir(bundle))
        want = {"flight_router.json", "metrics.prom", "router.json",
                "slo_timelines.json", "manifest.json",
                "stderr_decode0.txt"}
        want |= {
            f"flight_{n}.json" for n in workers if n != "decode0"
        }
        missing = want - have
        if missing:
            return fail(f"incident bundle missing {sorted(missing)}")
        with open(os.path.join(bundle, "manifest.json"),
                  encoding="utf-8") as fp:
            manifest = json.load(fp)
        if "decode0" not in manifest.get("dead", []):
            return fail(f"manifest does not name the dead worker: "
                        f"{manifest.get('dead')}")
        # The dead replica's scrape must tick the error counter, not
        # crash the poller.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            router.scrape_metrics(force=True)
            snap = router.snapshot()
            if snap.get("scrape_errors_total", {}).get(
                "decode0", 0
            ) >= 1:
                break
            time.sleep(0.1)
        else:
            return fail(
                "dead replica never bumped "
                "router_replica_scrape_errors_total"
            )
        print(
            f"# fleet obs smoke: SIGKILL pid {victim.pid} -> bundle "
            f"{os.path.basename(bundle)} with {len(have)} artifact(s) "
            "incl. surviving flight dumps + dead stderr tail"
        )
    finally:
        router.close()
        fleet.stop()
    print("FLEET_OBS_SMOKE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
