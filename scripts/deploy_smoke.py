#!/usr/bin/env python
"""Live-rollout smoke — the train -> export
-> deploy loop end to end on a REAL multi-process fleet
(serving/deploy.py, docs/serving.md "Deploys"):

1. Fit a tiny gpt2 for one epoch (Trainer + SyntheticTokens) and
   export it — manifest + weights fingerprint included.
2. Spin a 2-process fleet on the seed init, put open-loop traffic on
   it, and ``Router.deploy`` the export MID-LOAD: new-generation
   worker processes spawn from the checkpoint (shared on-disk compile
   cache), warm off-path, take the canary slice, ramp to 100% and
   retire the old workers.  The client must see ZERO errors (no
   dropped streams), the old steady fleet's per-process compile counts
   must not move, and the promoted fleet must serve the TRAINED
   weights byte-identical to in-driver ``generate()``.
3. Deploy the same export again through a wedged factory (canary-only
   TTFT regression): the SLO-burn watch must roll back within one
   burn window, restore the pre-deploy replica set, and the stable
   slice's outputs must stay byte-identical throughout.

Prints ``DEPLOY_SMOKE OK`` / ``DEPLOY_SMOKE FAIL: <why>``; non-zero
exit on any violation.  CPU-only, tiny model, ~4 worker processes at
peak.
"""

import os
import shutil
import sys
import tempfile
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def fail(msg: str) -> int:
    print(f"DEPLOY_SMOKE FAIL: {msg}")
    return 1


def main() -> int:
    import jax

    from ml_trainer_tpu import Trainer
    from ml_trainer_tpu.checkpoint import (
        load_model_manifest, load_model_variables,
    )
    from ml_trainer_tpu.data import SyntheticTokens
    from ml_trainer_tpu.generate import generate
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving import DeployConfig, SloPolicy
    from ml_trainer_tpu.serving.fleet import Fleet
    from ml_trainer_tpu.serving.loadgen import (
        ScheduledRequest, run_open_loop, schedule_from_trace,
        schedule_to_records,
    )

    work_dir = tempfile.mkdtemp(prefix="deploy_smoke_")
    ckpt_dir = os.path.join(work_dir, "export")

    # -- leg 1: train + export (the rollout target) -------------------
    model = get_model("gpt2_tiny", max_len=64)
    ds = SyntheticTokens(size=32, seq_len=16,
                         vocab_size=model.vocab_size, seed=0)
    Trainer(model, datasets=(ds, ds), epochs=1, batch_size=8,
            metric=None, model_dir=ckpt_dir, seed=7, lr=0.01).fit()
    manifest = load_model_manifest(ckpt_dir) or {}
    fp = manifest.get("weights_fingerprint")
    if not (fp and fp.startswith("w:")):
        return fail(f"export manifest missing weights fingerprint: "
                    f"{manifest}")
    trained = load_model_variables(ckpt_dir)
    seed_vars = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    print(f"# deploy smoke: trained + exported gpt2_tiny ({fp})")

    rng = np.random.default_rng(0)
    fleet = Fleet(
        roles=["both", "both"], model_name="gpt2_tiny", max_len=64,
        max_batch=4, max_queue=64, kv_page_size=8, seed=0,
        prefix_cache=False,
    )
    fleet.start()
    router = fleet.make_router(
        slo=SloPolicy(ttft_ms=2000.0, tpot_ms=2000.0, target=0.9),
        slo_timelines=256, hedging=False,
    )
    try:
        host, port = router.serve_http(port=0)
        url = f"http://{host}:{port}"
        # 2 canary tenants + 6 stable ones (slice at 0.25).
        tenants = (
            [t for t in (f"t{i}" for i in range(64))
             if router.tenant_slice(t) < 0.25][:2]
            + [t for t in (f"t{i}" for i in range(64))
               if router.tenant_slice(t) >= 0.25][:6]
        )
        rows = [
            ScheduledRequest(
                arrival_s=float(i * 0.12),
                tenant=tenants[i % len(tenants)],
                prompt=rng.integers(
                    0, model.vocab_size, int(rng.integers(8, 17))
                ).astype(np.int32),
                max_new_tokens=8,
            )
            for i in range(16)
        ]
        trace = schedule_from_trace(schedule_to_records(rows))
        refs_seed = [
            [int(t) for t in np.asarray(
                generate(model, seed_vars, s.prompt[None],
                         s.max_new_tokens))[0]]
            for s in trace
        ]
        refs_trained = [
            [int(t) for t in np.asarray(
                generate(model, trained, s.prompt[None],
                         s.max_new_tokens))[0]]
            for s in trace
        ]
        for _ in range(2):  # untimed: workers compile to steady state
            run_open_loop(trace, url=url, time_scale=0.0)

        def worker_compiles():
            out = {}
            for rep in list(router.replicas.values()):
                try:
                    out[rep.name] = int(
                        rep.server._get("/v1/spec")["compiles"] or 0
                    )
                except Exception:
                    pass
            return out

        class Load:
            def __init__(self):
                self.passes = []
                self.stop = threading.Event()
                self.thread = threading.Thread(
                    target=self._run, daemon=True)
                self.thread.start()

            def _run(self):
                while not self.stop.is_set():
                    self.passes.append(run_open_loop(
                        trace, url=url, collect_tokens=True))

            def finish(self):
                self.stop.set()
                self.thread.join(timeout=600.0)
                return (
                    sum(p["n_errors"] for p in self.passes),
                    [r for p in self.passes for r in zip(
                        p["per_request"],
                        range(len(p["per_request"])))],
                )

        cfg = DeployConfig(
            canary=0.25, stages=(1.0,), hold_s=1.0,
            burn_threshold=2.0, high_polls=2, window_s=10.0,
            min_window_requests=2, stage_min_requests=2,
            poll_interval_s=0.3, drain_timeout_s=60.0,
        )

        # -- leg 2: healthy mid-load deploy ---------------------------
        steady_base = worker_compiles()
        load = Load()
        dep = router.deploy(ckpt_dir, canary=0.25, config=cfg)
        state = dep.wait(timeout=600.0)
        steady_after = {
            n: c for n, c in worker_compiles().items()
            if n in steady_base
        }
        n_errors, outs = load.finish()
        dep.close()
        if state != "done":
            return fail(f"healthy deploy ended '{state}', not done "
                        f"(cause: {dep.rollback_cause})")
        if dep.weights_fp != fp:
            return fail(f"served fingerprint {dep.weights_fp} != "
                        f"export manifest {fp}")
        if n_errors:
            return fail(f"{n_errors} client error(s) (dropped streams) "
                        "during the healthy deploy")
        for r, i in outs:
            if r.get("output") not in (refs_seed[i], refs_trained[i]):
                return fail(f"mid-deploy output {i} matches neither "
                            "generation's generate()")
        # The steady fleet's compiles must not move while the deploy
        # runs (old workers that retired cleanly drop out of the
        # post-sample; every one still answering must be unchanged).
        moved = {n: steady_after[n] - steady_base[n]
                 for n in steady_after
                 if steady_after[n] != steady_base[n]}
        if moved:
            return fail(f"steady-fleet compiles moved mid-deploy: "
                        f"{moved}")
        out = [int(t) for t in np.asarray(
            router.complete(trace[0].prompt, 8, timeout=300))]
        if out != refs_trained[0]:
            return fail("promoted fleet output != generate() on the "
                        "trained export")
        print(f"# deploy smoke: mid-load deploy done, "
              f"{len(load.passes)} client pass(es), 0 errors, promoted "
              f"fleet byte-identical")

        # -- leg 3: forced regression -> auto-rollback ----------------
        base_factory = fleet.deploy_factory(ckpt_dir)

        def wedged_factory(role):
            remote = base_factory(role)
            orig = remote.submit_request

            def slow_submit(req):
                time.sleep(3.0)
                return orig(req)

            remote.submit_request = slow_submit
            return remote

        pre_replicas = sorted(router.replicas)
        load = Load()
        dep = router.deploy(ckpt_dir, canary=0.25,
                            factory=wedged_factory, config=cfg)
        state = dep.wait(timeout=600.0)
        n_errors, outs = load.finish()
        dep.close()
        if state != "rolled_back":
            return fail(f"forced regression ended '{state}', not "
                        "rolled_back")
        if "canary burn" not in (dep.rollback_cause or ""):
            return fail(f"rollback cause not burn-driven: "
                        f"{dep.rollback_cause}")
        if n_errors:
            return fail(f"{n_errors} client error(s) (dropped streams) "
                        "during the rollback")
        for r, i in outs:
            if r.get("output") != refs_trained[i]:
                return fail(f"output {i} diverged during the rollback "
                            "(gen2 shares gen1 weights; all outputs "
                            "must match)")
        if sorted(router.replicas) != pre_replicas:
            return fail(f"rollback did not restore the replica set: "
                        f"{sorted(router.replicas)} != {pre_replicas}")
        events = dep.report()["events"]
        first_burn = next(
            (e["t"] for e in events if e["action"] == "burn_high"),
            None,
        )
        rolled = next(
            (e["t"] for e in events if e["action"] == "transition"
             and e.get("to") == "rolled_back"), None,
        )
        if first_burn is None or rolled is None:
            return fail("rollback left no burn_high/rolled_back events")
        if rolled - first_burn > cfg.window_s:
            return fail(f"rollback took {rolled - first_burn:.1f}s — "
                        f"outside the {cfg.window_s}s burn window")
        print("# deploy smoke: forced regression rolled back inside one "
              "burn window, 0 errors, fleet restored")
    finally:
        try:
            router.close()
        finally:
            fleet.stop()
            shutil.rmtree(work_dir, ignore_errors=True)
    print("DEPLOY_SMOKE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
