#!/usr/bin/env python
"""Elastic-training chaos smoke — the ROADMAP item #1 success metric,
end to end with real OS processes: kill one of N simulated hosts
mid-run and the job finishes with a bit-exact-resumable history and
bounded steps-lost (resilience/elastic.py, docs/resilience.md).

Two legs, each phase a fresh subprocess so device counts can differ:

1. **In-process reshape** (the drain→reshape→continue controller): an
   8-device simulated 2-host cluster loses host 1 to a deterministic
   ``host_kill`` fault mid-epoch; the SAME ``fit()`` call drains,
   reshapes to 4 devices and finishes.  Asserted: trajectory equals the
   uninterrupted reference (preserve-global policy changes placement,
   not math), zero steps lost, the reshape record/topology, and that a
   fresh 4-device process resumes the survivor's checkpoints with a
   BIT-EXACT history continuation.

2. **Cross-process restart** (``--quick`` skips it): a REAL 2-process
   ``jax.distributed`` cluster (the mp_worker pattern) loses host 1 to
   a hard ``os._exit`` mid-step — no emergency checkpoint, the
   SIGKILL'd-pod-host case.  The driver reaps the survivor and restarts
   at a different topology (1 process, 2 devices) with
   ``fit(resume=True)``.  Asserted: completion, finite history, and
   steps-lost bounded by the ``save_every_steps`` cadence.

Prints ``ELASTIC_SMOKE_RESULT {json}`` and exits non-zero on any
violation.
"""

import json
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KILL_STEP = 6          # epoch 2, batch 2 of 4 (mid-epoch drain)
SAVE_EVERY = 2         # restart leg: step-checkpoint cadence = loss bound
MP_KILL_STEP = 6


# ----------------------------------------------------------- worker modes
def _worker_preamble(ndev: int):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={ndev}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, REPO)


def _make_trainer(workdir, ndev, **kw):
    from ml_trainer_tpu import MLModel, Trainer
    from ml_trainer_tpu.data import SyntheticCIFAR10

    if ndev is not None:
        kw["mesh_shape"] = {"data": ndev}  # else the default pod mesh
    return Trainer(
        MLModel(),
        datasets=(SyntheticCIFAR10(size=64, seed=0),
                  SyntheticCIFAR10(size=32, seed=1)),
        epochs=kw.pop("epochs", 3), batch_size=16, model_dir=workdir,
        metric=None, lr=0.01, seed=7, optimizer="adam", **kw,
    )


def worker_ref(workdir: str) -> int:
    _worker_preamble(8)
    t = _make_trainer(workdir, 8)
    t.fit()
    print(f"LOSSES {t.train_losses}", flush=True)
    return 0


def worker_chaos(workdir: str) -> int:
    _worker_preamble(8)
    os.environ["ML_TRAINER_TPU_FAULTS"] = (
        f"host_kill@step={KILL_STEP},host=1"
    )
    t = _make_trainer(workdir, 8, elastic=2)
    t.fit()
    assert not t.preempted, "elastic run exited preempted"
    assert int(t.mesh.size) == 4, f"mesh not reshaped: {t.mesh}"
    assert len(t.history["reshapes"]) == 1, t.history["reshapes"]
    rec = t.history["reshapes"][0]
    assert rec["old_topology"] == {"data": 8}, rec
    assert rec["new_topology"] == {"data": 4}, rec
    assert rec["steps_lost"] == 0, rec
    kinds = [r["kind"] for r in t._flight.records()]
    assert "reshape" in kinds, kinds
    from ml_trainer_tpu.telemetry import goodput

    assert goodput.snapshot()["reshape"] > 0.0, goodput.snapshot()
    print(f"RESHAPE {json.dumps(rec)}", flush=True)
    print(f"LOSSES {t.train_losses}", flush=True)
    return 0


def worker_resume(workdir: str) -> int:
    # A fresh process at the POST-reshape topology resumes the chaos
    # run's checkpoints: the reported history must be bit-exact.
    _worker_preamble(4)
    t = _make_trainer(workdir, 4, epochs=4)
    t.fit(resume=True)
    print(f"LOSSES {t.train_losses}", flush=True)
    return 0


def worker_mphost(port: str, pid: str, workdir: str) -> int:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=2"
    ).strip()
    os.environ["ML_TRAINER_TPU_FAULTS"] = (
        f"host_kill@step={MP_KILL_STEP},host=1"
    )
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=f"localhost:{port}", num_processes=2,
        process_id=int(pid),
    )
    sys.path.insert(0, REPO)
    t = _make_trainer(
        workdir, None, epochs=2, save_every_steps=SAVE_EVERY,
        is_parallel=True, backend="cpu",
    )
    t.fit()  # host 1 never returns (os._exit inside the loop)
    print(f"LOSSES {t.train_losses}", flush=True)
    return 0


def worker_mpresume(workdir: str) -> int:
    _worker_preamble(2)
    from ml_trainer_tpu import checkpoint as ckpt

    latest = ckpt.latest_valid_checkpoint(
        os.path.join(workdir, "checkpoints"), quarantine=False
    )
    assert latest is not None, "no committed checkpoint survived the kill"
    with open(os.path.join(latest, "manifest.json")) as fp:
        manifest = json.load(fp)
    mid = (manifest.get("history") or {}).get("mid_epoch") or {}
    cursor = {
        "epoch": manifest.get("epoch"),
        "batches_done": mid.get("batches_done", 0),
        "mesh": manifest.get("mesh"),
    }
    print(f"CURSOR {json.dumps(cursor)}", flush=True)
    t = _make_trainer(workdir, 2, epochs=2)
    t.fit(resume=True)
    assert len(t.train_losses) == 2, t.train_losses
    print(f"LOSSES {t.train_losses}", flush=True)
    return 0


# ------------------------------------------------------------ orchestrator
def _spawn(args, env_extra=None):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.pop("ML_TRAINER_TPU_FAULTS", None)
    env.update(env_extra or {})
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker", *args],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env, cwd=REPO,
    )


def _run_phase(args, timeout=240):
    proc = _spawn(args)
    out, _ = proc.communicate(timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"phase {args[0]} failed (rc={proc.returncode}):\n{out}")
    return out


def _parse(out: str, tag: str):
    line = next(
        ln for ln in out.splitlines() if ln.startswith(tag + " ")
    )
    payload = line[len(tag) + 1:]
    return json.loads(payload) if payload.lstrip().startswith(
        ("{", "[")
    ) else eval(payload)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _close(a, b, rel=2e-4):
    return len(a) == len(b) and all(
        abs(x - y) <= rel * max(abs(x), abs(y), 1e-12) for x, y in zip(a, b)
    )


def leg_in_process(workdir: str) -> dict:
    ref_out = _run_phase(["ref", os.path.join(workdir, "ref")])
    chaos_dir = os.path.join(workdir, "chaos")
    chaos_out = _run_phase(["chaos", chaos_dir])
    resume_out = _run_phase(["resume", chaos_dir])
    ref = _parse(ref_out, "LOSSES")
    chaos = _parse(chaos_out, "LOSSES")
    reshape = _parse(chaos_out, "RESHAPE")
    resumed = _parse(resume_out, "LOSSES")
    traj_equal = _close(chaos, ref)
    # Bit-exact-resumable: the 4-device process re-reports the chaos
    # run's history from its checkpoints EXACTLY, then extends it.
    resumable = len(resumed) == 4 and resumed[:3] == chaos
    return {
        "ok": bool(
            traj_equal and resumable and reshape["steps_lost"] == 0
        ),
        "trajectory_equal": traj_equal,
        "bit_exact_resumable": resumable,
        "steps_lost": reshape["steps_lost"],
        "old_topology": reshape["old_topology"],
        "new_topology": reshape["new_topology"],
        "trigger": reshape["trigger"],
        "losses": {"ref": ref, "chaos": chaos, "resumed": resumed},
    }


def leg_restart(workdir: str) -> dict:
    port = _free_port()
    mp_dir = os.path.join(workdir, "mp")
    procs = [
        _spawn(["mphost", str(port), str(pid), mp_dir]) for pid in (0, 1)
    ]
    victim = procs[1]
    try:
        victim.communicate(timeout=180)
    except subprocess.TimeoutExpired:
        victim.kill()
        victim.communicate(timeout=10)
        raise RuntimeError("host 1 did not die on its host_kill fault")
    if victim.returncode != 113:
        out0, _ = procs[0].communicate(timeout=10)
        raise RuntimeError(
            f"host 1 exited rc={victim.returncode}, expected the "
            f"host_kill hard-exit 113\n{out0}"
        )
    # The survivor blocks in a collective its peer never joins (or dies
    # on a gloo error) — the driver's correlated teardown is the
    # real-world whole-job SIGKILL.
    try:
        procs[0].communicate(timeout=8)
    except subprocess.TimeoutExpired:
        procs[0].kill()
        procs[0].communicate(timeout=10)
    out = _run_phase(["mpresume", mp_dir], timeout=240)
    cursor = _parse(out, "CURSOR")
    losses = _parse(out, "LOSSES")
    steps_per_epoch = 4  # 64 samples / global batch 16
    committed = (
        int(cursor["epoch"]) * steps_per_epoch
        if not cursor["batches_done"]
        else (int(cursor["epoch"]) - 1) * steps_per_epoch
        + int(cursor["batches_done"])
    )
    steps_lost = (MP_KILL_STEP - 1) - committed  # the kill pre-empted step 6
    finite = all(
        isinstance(v, float) and v == v and abs(v) != float("inf")
        for v in losses
    )
    return {
        "ok": bool(
            0 <= steps_lost <= SAVE_EVERY and len(losses) == 2 and finite
        ),
        "steps_lost": steps_lost,
        "steps_lost_bound": SAVE_EVERY,
        "committed_steps": committed,
        "kill_step": MP_KILL_STEP,
        "saved_mesh": cursor.get("mesh"),
        "losses": losses,
    }


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "--worker":
        mode, args = sys.argv[2], sys.argv[3:]
        return {
            "ref": worker_ref,
            "chaos": worker_chaos,
            "resume": worker_resume,
            "mphost": worker_mphost,
            "mpresume": worker_mpresume,
        }[mode](*args)
    quick = "--quick" in sys.argv[1:]
    import tempfile

    workdir = tempfile.mkdtemp(prefix="elastic_smoke_")
    result = {"in_process": leg_in_process(workdir)}
    if not quick:
        result["restart"] = leg_restart(workdir)
    result["ok"] = all(
        leg["ok"] for leg in result.values() if isinstance(leg, dict)
    )
    print(f"ELASTIC_SMOKE_RESULT {json.dumps(result)}", flush=True)
    if not result["ok"]:
        print("ELASTIC_SMOKE FAIL", flush=True)
        return 1
    ip = result["in_process"]
    msg = (
        f"ELASTIC_SMOKE OK: reshape {ip['old_topology']} -> "
        f"{ip['new_topology']} mid-run, trajectory equal, history "
        f"bit-exact-resumable, {ip['steps_lost']} step(s) lost"
    )
    if "restart" in result:
        rs = result["restart"]
        msg += (
            f"; hard-kill restart lost {rs['steps_lost']} step(s) "
            f"(bound {rs['steps_lost_bound']})"
        )
    print(msg, flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.exit(main())
