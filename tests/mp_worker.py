"""Worker process for tests/test_multiprocess.py.

Each worker is one "host" of a 2-process CPU cluster: 4 local virtual
devices, ``jax.distributed.initialize`` rendezvous, then the code paths
that are dead under the usual single-process simulated mesh (SURVEY.md §4
implication (c)): the per-host sampler split + multi-host prefetch
assembly (``make_array_from_process_local_data``), rank-0 checkpointing
with the broadcast resume, the cross-host desync detector — including a
forced-desync negative case with registry/flight forensics — and the
distributed-observability layer (telemetry/cluster.py): real cross-host
heartbeat aggregation into ``cluster_*{host=...}`` series, plus a
forced-slow host tripping the straggler detector.

Usage: python mp_worker.py <coordinator_port> <process_id> <workdir>
"""

import os
import sys

port, pid, workdir = sys.argv[1], int(sys.argv[2]), sys.argv[3]
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
).strip()
# Per-host flight-dump dir (the workers share `workdir` as their shared
# checkpoint storage; dumps are asserted per host below).
flight_dir = os.path.join(workdir, f"flight_host{pid}")
os.environ["ML_TRAINER_TPU_FLIGHT_DIR"] = flight_dir

import jax  # noqa: E402

# CPU pin, in process.
jax.config.update("jax_platforms", "cpu")
# Cross-process CPU computations (the jitted psum inside
# broadcast_one_to_all / process_allgather, and device_put's cross-host
# value check) need a CPU collectives backend; without gloo the runtime
# raises "Multiprocess computations aren't implemented on the CPU
# backend".  Must be set before the first device use.
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(
    coordinator_address=f"localhost:{port}", num_processes=2, process_id=pid
)
assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()
assert len(jax.local_devices()) == 4

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ml_trainer_tpu import MLModel, Trainer  # noqa: E402
from ml_trainer_tpu.data import SyntheticCIFAR10  # noqa: E402
from ml_trainer_tpu.parallel.desync import check_desync  # noqa: E402
from ml_trainer_tpu.utils.functions import (  # noqa: E402
    custom_pre_process_function,
)

transform = custom_pre_process_function()  # normalize — raw 0-255 pixels
# make the loss scale meaningless for the cross-rank equality check
datasets = (
    SyntheticCIFAR10(size=64, seed=0, transform=transform),
    SyntheticCIFAR10(size=32, seed=1, transform=transform),
)
common = dict(
    batch_size=16, model_dir=workdir, is_parallel=True, backend="cpu",
    seed=5, lr=0.001, optimizer="adam", metric=None,
    # Distributed observability rides the telemetry flag: heartbeats at
    # every sync, ONE cluster allgather per epoch (telemetry/cluster.py).
    # The factor is cranked way up so NATURAL skew between two worker
    # processes sharing one CPU never fires; the forced-straggler test
    # below tightens it deterministically.
    telemetry=True, log_every_steps=1, straggler_factor=50.0,
)

# --- multi-host training: sampler split + prefetch assembly + desync check
t = Trainer(MLModel(), datasets=datasets, epochs=2, **common)
sampler = t.train_loader.sampler
assert getattr(sampler, "num_replicas", 1) == 2, sampler
t.fit()
assert all(np.isfinite(v) for v in t.train_losses)
print(f"LOSSES {t.train_losses}", flush=True)

# --- healthy state: fingerprints agree across hosts
check_desync({"params": t.state.params})
print("DESYNC_CLEAN_OK", flush=True)

# --- cluster aggregation: EVERY host's registry now carries both hosts'
# heartbeat series (the allgather republishes the whole pod everywhere,
# so host 0's scrape covers it — and so does this host's assert).
from ml_trainer_tpu.telemetry import default_registry  # noqa: E402

snap = default_registry().snapshot()
for h in (0, 1):
    assert f"cluster_last_step{{host={h}}}" in snap, sorted(
        k for k in snap if k.startswith("cluster_")
    )
    assert snap[f"cluster_last_step{{host={h}}}"] > 0, snap
assert snap.get("cluster_hosts") == 2, snap
print("CLUSTER_AGG_OK", flush=True)

# --- forced straggler: host 1 reports a 10x step time into its
# heartbeat; the next aggregation must fire the detector on BOTH hosts'
# registries (the gathered view is identical) naming host 1.
ct = t._cluster
ct.straggler_factor = 2.0  # identical on both hosts: detection stays
# deterministic (it runs on the gathered matrix, same on every host)
base_ms = max(float(snap["cluster_step_ms_p50{host=0}"]), 1.0)
ct.heartbeat(step_ms_p50=base_ms * (10.0 if pid == 1 else 1.0))
ct.sync(step=12345)
snap = default_registry().snapshot()
assert snap.get("cluster_straggler_events_total{host=1}", 0) >= 1, snap
assert "cluster_straggler_events_total{host=0}" not in snap or (
    snap["cluster_straggler_events_total{host=0}"] == 0
), snap
straggler_recs = [
    r for r in t._flight.records() if r["kind"] == "straggler"
]
assert straggler_recs and straggler_recs[-1]["host"] == 1, straggler_recs
assert straggler_recs[-1]["step"] == 12345, straggler_recs
print("STRAGGLER_OK", flush=True)

# --- resume: host 0 finds the checkpoint, decision + state broadcast
t2 = Trainer(MLModel(), datasets=datasets, epochs=3, **common)
t2.fit(resume=True)
assert len(t2.train_losses) == 3, t2.train_losses
assert t2.train_losses[:2] == t.train_losses, (t2.train_losses, t.train_losses)
print(f"RESUME_OK {t2.train_losses}", flush=True)

# --- forced desync: perturb THIS host's local replica only (host-local
# numpy copies; a global-array op would need every process to join in)
local = jax.tree.map(
    lambda p: np.asarray(p.addressable_data(0)), t2.state.params
)
if pid == 1:
    local = jax.tree.map(lambda a: a + 100.0, local)
try:
    check_desync(local, step=777)
    detected = False
except RuntimeError:
    detected = True
# Only the diverged (non-zero) host compares against host 0's broadcast.
assert detected == (pid == 1), (detected, pid)
print("DESYNC_FORCED_OK", flush=True)

# --- desync forensics: every host published its fingerprint; the
# diverging host ALSO left a flight record + an on-disk dump naming
# itself and the step, all BEFORE the RuntimeError above unwound.
snap = default_registry().snapshot()
assert f"cluster_param_fingerprint{{host={pid}}}" in snap, sorted(
    k for k in snap if k.startswith("cluster_param")
)
from ml_trainer_tpu.telemetry.flight import get_recorder  # noqa: E402

desync_recs = [
    r for r in get_recorder().records() if r["kind"] == "desync"
]
if pid == 1:
    assert desync_recs, "diverging host recorded no desync event"
    assert desync_recs[-1]["host"] == 1, desync_recs
    assert desync_recs[-1]["step"] == 777, desync_recs
    assert snap.get("cluster_desync_events_total", 0) >= 1, snap
    import json  # noqa: E402

    dumps = sorted(
        f for f in os.listdir(flight_dir) if f.startswith("flight_")
    )
    assert dumps, "diverging host wrote no flight dump"
    payloads = [
        json.load(open(os.path.join(flight_dir, f))) for f in dumps
    ]
    desync_dumps = [p for p in payloads if p["reason"] == "desync"]
    assert desync_dumps, [p["reason"] for p in payloads]
    assert desync_dumps[-1]["host"] == 1 and desync_dumps[-1]["step"] == 777
else:
    assert not desync_recs, desync_recs
print("DESYNC_FORENSICS_OK", flush=True)
print("WORKER_DONE", flush=True)
