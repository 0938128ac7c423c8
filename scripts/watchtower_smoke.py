#!/usr/bin/env python
"""Watchtower smoke — the PR 20 tentpole end
to end against a REAL 3-process fleet slice (1 prefill + 2 decode over
HTTP), proving the observability plane is free and the alerting path is
live:

1. **Free** — with the TSDB sampling on every scrape, the dashboard
   served, and the alert engine evaluating each poll tick, the fleet
   still serves a seeded trace byte-identical to in-driver
   ``generate()`` with ZERO post-warmup compiles per worker.
2. **Live dashboard** — ``GET /dash`` on the router AND on a worker
   returns the self-contained HTML (inline sparklines, no assets).
3. **Detection** — a ``replica_slow`` chaos fault is armed in decode0's
   process via ``POST /admin/faults`` AFTER warmup; one more traffic
   pass (still byte-identical: throttled, not wrong) makes decode0's
   e2e observations jump, and a declarative severity-``page``
   :class:`AlertRule` installed at runtime
   (``quantile_over_time`` over the federated ``replica=decode0``
   series) fires within one evaluation window — producing the flight
   ``alert`` record AND a full incident bundle whose artifacts include
   ``dashboard.html`` (the TSDB snapshot at firing time) and
   ``alerts.json`` (rule states + history).

Prints ``WATCHTOWER_SMOKE OK`` / ``WATCHTOWER_SMOKE FAIL: <why>``;
non-zero exit on any violation.  CPU-only, tiny model.
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

RULE = "replica_slow_e2e"


def fail(msg: str) -> int:
    print(f"WATCHTOWER_SMOKE FAIL: {msg}")
    return 1


def main() -> int:
    import jax

    from ml_trainer_tpu.generate import generate
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving.fleet import Fleet
    from ml_trainer_tpu.serving.loadgen import (
        ScheduledRequest, run_open_loop, schedule_from_trace,
        schedule_to_records,
    )
    from ml_trainer_tpu.telemetry.alerts import AlertRule

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
    incident_root = tempfile.mkdtemp(prefix="watchtower-smoke-")
    router = fleet.make_router(
        hedging=False, metrics_scrape_interval=0.1,
        incident_dir=incident_root, incident_min_interval_s=0.0,
    )
    try:
        host, port = router.serve_http(port=0)
        url = f"http://{host}:{port}"

        # -- leg 1: the plane is free ----------------------------------
        for _ in range(2):  # untimed: workers compile to steady state
            run_open_loop(trace, url=url, time_scale=0.0)

        def compiles():
            return {
                n: int(r._get("/v1/spec")["compiles"] or 0)
                for n, r in fleet.replicas.items()
            }

        def check_identity(client, what: str):
            if client["n_errors"]:
                return f"{client['n_errors']} client error(s) ({what})"
            for r, ref in zip(client["per_request"], refs):
                if r.get("output") != ref:
                    return (
                        f"fleet output diverged from generate() {what}"
                    )
            return None

        before = compiles()
        client = run_open_loop(trace, url=url, collect_tokens=True)
        after = compiles()
        err = check_identity(client, "with the watchtower on")
        if err:
            return fail(err)
        fresh = {n: after[n] - before[n] for n in after}
        if any(fresh.values()):
            return fail(f"post-warmup worker recompiles: {fresh}")
        print(
            f"# watchtower smoke: {len(trace)} requests byte-identical "
            "across 3 processes with TSDB + alert engine + dashboard "
            "on, 0 post-warmup compiles"
        )

        # -- leg 2: live dashboards ------------------------------------
        router.scrape_metrics(force=True)
        router._watchtower_tick()
        for name, dash_url in [
            ("router", f"{url}/dash"),
            ("decode0", f"{fleet.replicas['decode0'].url}/dash"),
        ]:
            with urllib.request.urlopen(dash_url, timeout=10) as resp:
                ctype = resp.headers.get("Content-Type", "")
                html = resp.read().decode()
            if "text/html" not in ctype:
                return fail(f"{name} /dash content-type {ctype!r}")
            if "<html" not in html or "svg" not in html:
                return fail(
                    f"{name} /dash is not the sparkline dashboard"
                )
        if f"{len(router.watchtower)}" == "0":
            return fail("router TSDB empty after scrape+tick")
        print(
            f"# watchtower smoke: GET /dash live on router + worker, "
            f"router TSDB holds {len(router.watchtower)} series"
        )

        # -- leg 3: chaos -> declarative page -> incident bundle -------
        router.add_alert_rule(AlertRule(
            RULE,
            "quantile(0.9, serving_e2e_seconds{replica=decode0}[60s])"
            " > 0.5",
            severity="page",
            description="decode0 e2e q90 regressed (replica_slow)",
        ))
        victim = fleet.replicas["decode0"]
        spec = f"replica_slow@host={victim.replica_index},secs=3"
        resp = victim._post("/admin/faults", {"spec": spec})
        if not resp.get("ok"):
            return fail(f"fault install rejected: {resp}")
        client = run_open_loop(trace, url=url, collect_tokens=True)
        err = check_identity(client, "under replica_slow chaos")
        if err:
            return fail(err)

        # One evaluation window: the next scrape carries the regressed
        # observations; the first evaluate over it must fire.
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            router.scrape_metrics(force=True)
            router._watchtower_tick()
            if router.alerts.rule(RULE).firing():
                break
            time.sleep(0.1)
        else:
            return fail(
                f"rule {RULE} never fired after replica_slow "
                f"(history: {router.alerts.history()[-3:]})"
            )
        fired = [
            ev for ev in router.alerts.history()
            if ev["rule"] == RULE and ev["state"] == "firing"
        ]
        if not fired:
            return fail("rule firing but no firing event in history")

        deadline = time.monotonic() + 60
        bundle = None
        while time.monotonic() < deadline:
            bundle = router.last_incident_path
            if bundle and os.path.exists(
                os.path.join(bundle, "manifest.json")
            ):
                break
            time.sleep(0.1)
        else:
            return fail("page alert never assembled an incident bundle")
        have = set(os.listdir(bundle))
        for want in ("dashboard.html", "alerts.json",
                     "flight_router.json", "manifest.json",
                     "metrics.prom"):
            if want not in have:
                return fail(f"incident bundle missing {want}")
        with open(os.path.join(bundle, "manifest.json"),
                  encoding="utf-8") as fp:
            manifest = json.load(fp)
        if RULE not in str(manifest.get("reason", "")):
            return fail(
                f"bundle reason does not name the rule: "
                f"{manifest.get('reason')!r}"
            )
        with open(os.path.join(bundle, "alerts.json"),
                  encoding="utf-8") as fp:
            alerts = json.load(fp)
        if not any(
            ev.get("rule") == RULE and ev.get("state") == "firing"
            for ev in alerts.get("history", [])
        ):
            return fail("bundle alerts.json lacks the firing event")
        with open(os.path.join(bundle, "dashboard.html"),
                  encoding="utf-8") as fp:
            dash = fp.read()
        if RULE not in dash:
            return fail(
                "bundle dashboard.html does not render the alert"
            )
        with open(os.path.join(bundle, "flight_router.json"),
                  encoding="utf-8") as fp:
            flight = fp.read()
        if '"alert"' not in flight or RULE not in flight:
            return fail(
                "router flight dump lacks the alert record"
            )
        print(
            f"# watchtower smoke: replica_slow on decode0 -> {RULE} "
            f"fired (value {fired[0].get('value')}), bundle "
            f"{os.path.basename(bundle)} holds dashboard.html + "
            "alerts.json + flight alert record"
        )
    finally:
        router.close()
        fleet.stop()
    print("WATCHTOWER_SMOKE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
