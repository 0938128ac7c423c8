"""graft-lint: seeded known-bad fixtures + zero-false-positive pins.

Two halves, mirroring the subsystem:

* every checker FIRES on a fixture built to violate its contract
  (mismatched ppermute across switch branches, fp32 matmul under the
  bf16 policy, an undonated aliasable buffer, a lock cycle, ``.item()``
  in a registered hot loop, an unused import);
* every checker stays SILENT on the real tree — the AST pack over the
  actual sources and the jaxpr checks over the actual traced
  train/decode/pipeline programs report zero findings, pinned
  non-vacuously (the traced programs demonstrably contain the
  constructs the checkers inspect).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ml_trainer_tpu.analysis import (
    Report,
    baseline_payload,
    check_collective_uniformity,
    check_dtype_policy,
    check_program,
    check_traceable,
    default_baseline_path,
    diff_against_baseline,
    modules_from_sources,
    run_ast_checks,
    scan_tree,
)
from ml_trainer_tpu.analysis import ast_checks, jaxpr_checks
from ml_trainer_tpu.analysis.findings import Finding, load_baseline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh2():
    return Mesh(np.array(jax.devices()[:2]), ("data",))


# ------------------------------------------------- collective uniformity
class TestCollectiveUniformity:
    def _switch_program(self, matched: bool):
        mesh = _mesh2()

        def body(x):
            def b0(v):
                return lax.ppermute(v, "data", [(0, 1), (1, 0)])

            def b1(v):
                perm = [(0, 1), (1, 0)] if matched else [(0, 1)]
                return lax.ppermute(v, "data", perm) * 2.0

            return lax.switch((x.sum() > 0).astype(jnp.int32), (b0, b1), x)

        f = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        ))
        return f.trace(jnp.ones((4, 2)))

    def test_mismatched_ppermute_across_branches_fires(self):
        traced = self._switch_program(matched=False)
        out = check_collective_uniformity(traced.jaxpr, "fixture")
        assert len(out) == 1
        assert out[0].rule == "collective-mismatch"
        assert out[0].severity == "error"
        # The finding carries both branches' wire programs.
        branches = out[0].details["branch_collectives"]
        assert len(branches) == 2 and branches[0] != branches[1]

    def test_matched_branches_pass(self):
        traced = self._switch_program(matched=True)
        assert check_collective_uniformity(traced.jaxpr, "fixture") == []

    def test_op_kind_mismatch_fires(self):
        mesh = _mesh2()

        def body(x):
            return lax.switch(
                (x.sum() > 0).astype(jnp.int32),
                (lambda v: lax.psum(v, "data"),
                 lambda v: lax.ppermute(v, "data", [(0, 1), (1, 0)])),
                x,
            )

        f = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
            check_vma=False,
        ))
        out = check_collective_uniformity(f.trace(jnp.ones((4,))).jaxpr,
                                          "fixture")
        assert [f_.rule for f_ in out] == ["collective-mismatch"]


# ------------------------------------------------------ dtype policy
class TestDtypePolicy:
    def test_fp32_matmul_under_bf16_fires(self):
        def f(a, b):
            return (a @ b).sum()

        traced = jax.jit(f).trace(
            jnp.ones((8, 8), jnp.float32), jnp.ones((8, 8), jnp.float32)
        )
        out = check_dtype_policy(traced.jaxpr, "fixture", "bf16")
        assert [x.rule for x in out] == ["fp32-compute-under-bf16"]
        assert out[0].details["primitive"] == "dot_general"

    def test_bf16_matmul_passes_and_fp32_policy_exempt(self):
        def f(a, b):
            return (a @ b).sum()

        bf = jax.jit(f).trace(
            jnp.ones((8, 8), jnp.bfloat16), jnp.ones((8, 8), jnp.bfloat16)
        )
        assert check_dtype_policy(bf.jaxpr, "fixture", "bf16") == []
        fp = jax.jit(f).trace(
            jnp.ones((8, 8), jnp.float32), jnp.ones((8, 8), jnp.float32)
        )
        assert check_dtype_policy(fp.jaxpr, "fixture", "fp32") == []

    def test_bf16_gradient_psum_fires(self):
        mesh = _mesh2()

        def body(g):
            return lax.psum(g, "data")

        f = jax.jit(shard_map(
            body, mesh=mesh, in_specs=P("data"), out_specs=P(),
            check_vma=False,
        ))
        traced = f.trace(jnp.ones((4, 4), jnp.bfloat16))
        out = check_dtype_policy(traced.jaxpr, "fixture", "bf16")
        assert "bf16-gradient-reduction" in [x.rule for x in out]


# ---------------------------------------------------- donation auditing
class TestDonationAudit:
    def _step(self):
        def step(state, x):
            return {"w": state["w"] + x.sum()}, x.mean()

        args = ({"w": jnp.ones((256, 256))}, jnp.ones((4, 4)))
        return step, args

    def test_undonated_aliasable_buffer_fires_with_priced_bytes(self):
        step, args = self._step()
        traced = jax.jit(step).trace(*args)
        out = jaxpr_checks.audit_donation(traced, "fixture",
                                          min_bytes=1 << 10)
        assert [f.rule for f in out] == ["undonated-buffer"]
        # Priced through the memory ledger: 256*256*4 bytes.
        assert out[0].details["undonated_bytes"] == 256 * 256 * 4

    def test_donated_step_passes_and_aliasing_verified(self):
        step, args = self._step()
        traced = jax.jit(step, donate_argnums=0).trace(*args)
        lowered = traced.lower().as_text()
        assert jaxpr_checks.audit_donation(
            traced, "fixture", min_bytes=1 << 10, lowered_text=lowered
        ) == []

    def test_small_buffers_below_threshold_ignored(self):
        step, args = self._step()
        traced = jax.jit(step).trace(*args)
        assert jaxpr_checks.audit_donation(
            traced, "fixture", min_bytes=1 << 20
        ) == []


# ------------------------------------------------------ host-sync probe
class TestHostSyncProbe:
    def test_item_in_step_fn_becomes_finding(self):
        def bad_step(x):
            return x * float(jnp.sum(x))  # forces the tracer to host

        out = check_traceable(
            lambda: jax.jit(bad_step).trace(jnp.ones((4,))), "bad_step"
        )
        assert [f.rule for f in out] == ["host-sync-in-program"]

    def test_clean_step_traces(self):
        assert check_traceable(
            lambda: jax.jit(lambda x: x * 2).trace(jnp.ones((4,))), "ok"
        ) == []


# ---------------------------------------------------------- lock order
_LOCK_CYCLE_SRC = {
    "pkg/a.py": """
import threading

class Engine:
    def __init__(self, cache: "Cache"):
        self._lock = threading.Lock()
        self._cache = cache
        self.jobs = 0

    def run(self):
        with self._lock:
            self.jobs += 1
            self._cache.get()
""",
    "pkg/b.py": """
import threading

class Cache:
    def __init__(self, engine: "Engine"):
        self._lock = threading.Lock()
        self._engine = engine

    def get(self):
        with self._lock:
            return 1

    def evict(self):
        with self._lock:
            self._engine.run()
""",
}


class TestLockOrder:
    def test_cycle_between_engine_and_cache_fires(self):
        modules = modules_from_sources(_LOCK_CYCLE_SRC)
        out = ast_checks.check_lock_order(modules)
        cycles = [f for f in out if f.rule == "lock-order-cycle"]
        # The A<->B inversion proper (evict holds Cache._lock and calls
        # into Engine.run which takes Engine._lock; run holds
        # Engine._lock and calls into Cache.get which takes
        # Cache._lock)...
        assert any(
            set(c.details["cycle"]) == {"Engine._lock", "Cache._lock"}
            for c in cycles
        )
        # ...and the transitive self-reacquisition evict->run->get also
        # latent in the fixture — both are genuine deadlocks.
        assert all(f.severity == "error" for f in cycles)

    def test_self_reacquire_plain_lock_fires_rlock_passes(self):
        src = """
import threading

class Box:
    def __init__(self):
        self._lock = threading.{kind}()
        self.n = 0

    def bump(self):
        with self._lock:
            self.n += 1

    def bump_twice(self):
        with self._lock:
            self.bump()
"""
        bad = modules_from_sources({"m.py": src.format(kind="Lock")})
        out = ast_checks.check_lock_order(bad)
        assert any(
            f.rule == "lock-order-cycle" and len(f.details["cycle"]) == 2
            for f in out
        )
        ok = modules_from_sources({"m.py": src.format(kind="RLock")})
        assert ast_checks.check_lock_order(ok) == []

    def test_ordered_nesting_passes(self):
        src = """
import threading

class A:
    def __init__(self):
        self._lock = threading.Lock()
        self._b = B()

    def run(self):
        with self._lock:
            self._b.get()

class B:
    def __init__(self):
        self._lock = threading.Lock()

    def get(self):
        with self._lock:
            return 1
"""
        modules = modules_from_sources({"m.py": src})
        assert ast_checks.check_lock_order(modules) == []


# ------------------------------------------------- unguarded shared state
class TestSharedState:
    _SRC = """
import threading

class Metrics:
    def __init__(self):
        self._lock = threading.Lock()
        self.count = 0

    def record(self):
        with self._lock:
            self.count += 1

    def reset(self):
        self.count = 0
"""

    def test_unguarded_mutation_fires(self):
        out = ast_checks.check_shared_state(
            modules_from_sources({"m.py": self._SRC})
        )
        assert [f.rule for f in out] == ["unguarded-shared-state"]
        assert out[0].details["attr"] == "count"

    def test_private_helper_called_under_lock_passes(self):
        src = self._SRC.replace("def reset(self):", "def _reset(self):") \
            .replace("self.count += 1", "self.count += 1\n            self._reset()")
        assert ast_checks.check_shared_state(
            modules_from_sources({"m.py": src})
        ) == []

    def test_caller_holds_the_lock_comment_honored(self):
        src = self._SRC.replace(
            "    def reset(self):",
            "    def reset(self):\n        # Caller holds the lock.",
        )
        assert ast_checks.check_shared_state(
            modules_from_sources({"m.py": src})
        ) == []


# --------------------------------------------------- host-only + hot-loop
class TestHostRules:
    def test_jax_import_in_scheduler_fires(self):
        out = ast_checks.check_host_only_modules(modules_from_sources({
            "ml_trainer_tpu/serving/scheduler.py":
                "import jax\nimport numpy as np\n",
        }))
        assert [f.rule for f in out] == ["device-op-in-host-module"]

    def test_item_in_hot_loop_fires_and_sync_ok_suppresses(self):
        body = """
import numpy as np

class SlotDecodeEngine:
    def step(self):
        toks = self.tok.item(){suffix}
        return toks
"""
        fires = ast_checks.check_host_sync(modules_from_sources({
            "ml_trainer_tpu/serving/engine.py": body.format(suffix=""),
        }))
        assert [f.rule for f in fires] == ["host-sync-hot-loop"]
        quiet = ast_checks.check_host_sync(modules_from_sources({
            "ml_trainer_tpu/serving/engine.py":
                body.format(suffix="  # graft-lint: sync-ok"),
        }))
        assert quiet == []

    def test_cold_functions_not_scanned(self):
        out = ast_checks.check_host_sync(modules_from_sources({
            "ml_trainer_tpu/serving/engine.py": (
                "class SlotDecodeEngine:\n"
                "    def admit(self):\n"
                "        return self.tok.item()\n"
            ),
        }))
        assert out == []


# ------------------------------------------------------- import hygiene
class TestImportHygiene:
    def test_unused_import_fires_noqa_and_init_exempt(self):
        out = ast_checks.check_unused_imports(modules_from_sources({
            "m.py": "import os\nimport json\nprint(json.dumps({}))\n",
        }))
        assert [f.rule for f in out] == ["unused-import"]
        assert "os" in out[0].message
        assert ast_checks.check_unused_imports(modules_from_sources({
            "m.py": "import os  # noqa\n",
        })) == []
        assert ast_checks.check_unused_imports(modules_from_sources({
            "pkg/__init__.py": "from pkg.sub import thing\n",
        })) == []

    def test_all_reexport_counts_as_use(self):
        assert ast_checks.check_unused_imports(modules_from_sources({
            "m.py": "from x import y\n__all__ = [\"y\"]\n",
        })) == []


# -------------------------------------------------------- baseline logic
class TestBaseline:
    def test_new_finding_fails_fixed_finding_reported(self):
        f1 = Finding("unused-import", "warn", "a.py:3", "'os' unused")
        f2 = Finding("lock-order-cycle", "error", "b.py:9", "cycle A-B")
        baseline = baseline_payload(Report([f1]))
        # Same findings -> ok; line drift does not break the key.
        moved = Finding("unused-import", "warn", "a.py:99", "'os' unused")
        assert diff_against_baseline(Report([moved]), baseline)["ok"]
        # A new rule violation -> fail, naming only the new one.
        d = diff_against_baseline(Report([moved, f2]), baseline)
        assert not d["ok"] and len(d["new"]) == 1
        assert d["new"][0]["rule"] == "lock-order-cycle"
        # A fixed finding is informational.
        d2 = diff_against_baseline(Report([]), baseline)
        assert d2["ok"] and len(d2["fixed"]) == 1
        # No baseline: everything is new.
        assert not diff_against_baseline(Report([moved]), None)["ok"]


# -------------------------------------------------- real-tree pins (0 FP)
class TestRealTreeClean:
    def test_ast_pack_zero_findings_on_real_tree(self):
        modules = scan_tree(REPO)
        assert len(modules) > 80  # the real tree, not an empty walk
        report = run_ast_checks(modules)
        assert report == [], Report(report).render()
        # The committed baseline is what this fresh run gives (the real
        # programs' half of it is TestRealProgramsClean's zero findings):
        # accepting a finding is a deliberate diff of that file.
        committed = load_baseline(default_baseline_path())
        assert committed == baseline_payload(Report(report))

    def test_fixed_modules_stay_import_clean(self):
        # Regression for the unused-import sweep this PR landed
        # (loader/bert/vit/collectives/ring/faults/scheduler/
        # compile_watch/memory).
        fixed = [
            "ml_trainer_tpu/data/loader.py",
            "ml_trainer_tpu/models/bert.py",
            "ml_trainer_tpu/models/vit.py",
            "ml_trainer_tpu/parallel/collectives.py",
            "ml_trainer_tpu/parallel/ring.py",
            "ml_trainer_tpu/resilience/faults.py",
            "ml_trainer_tpu/serving/scheduler.py",
            "ml_trainer_tpu/telemetry/compile_watch.py",
            "ml_trainer_tpu/telemetry/memory.py",
        ]
        modules = scan_tree(REPO, subdirs=("ml_trainer_tpu",))
        subset = {k: v for k, v in modules.items() if k in fixed}
        assert len(subset) == len(fixed)
        assert ast_checks.check_unused_imports(subset) == []

    def test_hot_loop_fences_stay_annotated(self):
        # Regression for the sync-point annotation sweep: every
        # intentional fence in the engine step loops and trainer epoch
        # loops carries its graft-lint annotation.
        modules = scan_tree(REPO, subdirs=("ml_trainer_tpu",))
        assert ast_checks.check_host_sync(modules) == []

    def test_host_modules_stay_device_free(self):
        modules = scan_tree(REPO, subdirs=("ml_trainer_tpu",))
        assert ast_checks.check_host_only_modules(modules) == []


class TestRealProgramsClean:
    def test_decode_programs_zero_findings_and_nonvacuous(self):
        from ml_trainer_tpu.analysis import programs as PR

        specs = PR.build_decode_specs(paged=True, spec_k=2)
        assert {s.name for s in specs} >= {
            "serve_decode[contiguous]", "serve_decode[paged]",
            "spec_verify[k2]",
        }
        all_findings = []
        donated_programs = 0
        for s in specs:
            all_findings += check_program(
                s.traced, s.name, policy=s.policy,
                min_donation_bytes=s.min_donation_bytes,
            )
            flat = jax.tree_util.tree_flatten_with_path(
                s.traced.args_info
            )[0]
            if any(getattr(i, "donated", False) for _, i in flat):
                donated_programs += 1
        assert all_findings == [], Report(all_findings).render()
        # Non-vacuous: the decode/insert family really does donate.
        assert donated_programs >= 3

    def test_train_programs_zero_findings_and_bf16_policy_holds(self):
        from ml_trainer_tpu.analysis import programs as PR

        specs = PR.build_train_specs()
        assert any("sharded" in s.name for s in specs)
        all_findings = []
        bf16_dots = 0
        sharded_reductions = 0
        for s in specs:
            all_findings += check_program(
                s.traced, s.name, policy=s.policy,
                min_donation_bytes=s.min_donation_bytes,
            )
            if s.policy == "bf16":
                for e in jaxpr_checks.iter_eqns(s.traced.jaxpr):
                    if e.primitive.name == "dot_general":
                        bf16_dots += 1
                    if "sharded" in s.name and e.primitive.name in (
                        "reduce_scatter", "all_gather", "psum"
                    ):
                        sharded_reductions += 1
        assert all_findings == [], Report(all_findings).render()
        # Non-vacuous: the bf16 programs carry real matmuls the dtype
        # rule inspected, and the sharded-dp step carries the bucketed
        # reduce-scatter/all-gather the reduction rule inspected (all
        # fp32 per the PR7 contract — a bf16 one would have fired).
        assert bf16_dots > 0
        assert sharded_reductions >= 3

    def test_pipeline_program_zero_findings_and_nonvacuous(self):
        from ml_trainer_tpu.analysis import programs as PR

        specs = PR.build_pipeline_specs()
        assert specs, "stage mesh unavailable on the 8-device harness?"
        s = specs[0]
        conds = sum(
            1 for e in jaxpr_checks.iter_eqns(s.traced.jaxpr)
            if e.primitive.name == "cond"
        )
        colls = sum(
            1 for e in jaxpr_checks.iter_eqns(s.traced.jaxpr)
            if e.primitive.name in jaxpr_checks.COLLECTIVE_PRIMS
        )
        # The tick-table engine is the switch+ppermute composition the
        # collective checker exists for.
        assert conds >= 2 and colls >= 2
        out = check_program(s.traced, s.name, policy=s.policy,
                            min_donation_bytes=s.min_donation_bytes)
        assert out == [], Report(out).render()


# ------------------------------------------------- flight-context provider
class TestFlightContext:
    def test_baseline_fingerprint_rides_flight_dumps(self, tmp_path):
        import json

        from ml_trainer_tpu.analysis import register_flight_context
        from ml_trainer_tpu.telemetry.flight import FlightRecorder

        rec = FlightRecorder(capacity=4, default_dir=str(tmp_path))
        register_flight_context(rec)
        rec.record("step", n=1)
        path = rec.dump("test")
        payload = json.load(open(path))
        ctx = payload["context"]["lint_baseline"]
        committed = load_baseline(default_baseline_path())
        assert ctx["present"] is True
        assert ctx["fingerprint"] == committed["fingerprint"]


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-q"]))
