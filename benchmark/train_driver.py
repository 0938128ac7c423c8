"""The ``train`` driver: one run of a training cell.

Drives ``Trainer.fit`` in-process: the default loader over the benchmark's
own token rows, ``prefetch_to_device``, the fused train step.  One Trainer is
built, driven from the seed through its first steps (which the reference
follows afterwards) and handed, the same object, to the window.

The Trainer has no per-step hook and no way to stop inside an epoch, and
every epoch ends in a validation pass and two saves.  So the run is one long
epoch, the step boundary is observed by wrapping the compiled step the
Trainer holds (``_train_step``: the wrapper calls it and looks at what comes
back, no more), and the epoch is left by an exception that ``fit`` does not
catch.  Both are listed in PERF.md for the tracing issue to replace.

The host runs ahead of the device (the Trainer fences every 50 steps), so
the window is closed by work, not by the host's clock: warm-up times a
fenced step, the window is ``round(seconds / that)`` steps between two
device fences, and the rate is every token of those steps over all the time
between the fences.
"""

from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from benchmark import flops, harness, reference, trace_reduce
from benchmark.harness import BenchError


class WindowClosed(BaseException):
    """Leaves ``Trainer.fit`` in mid-epoch.  Not an ``Exception``: fit's
    crash handler (flight dump, run report) is for crashes."""


def token_rows(seed: int, rows: int, seq_len: int, vocab: int):
    """Next-token rows from the seed, as ``SyntheticTokens`` shapes them:
    the target of the last position wraps to the row's first token."""
    rng = np.random.default_rng([int(seed), 0xDA7A])
    data = rng.integers(0, vocab, size=(rows, seq_len)).astype(np.int32)
    return data, np.roll(data, -1, axis=1)


def _dataset(data, targets, seen: list):
    from ml_trainer_tpu.data.datasets import ArrayDataset

    class ObservedRows(ArrayDataset):
        """The program's array dataset, noting which rows each batch took."""

        def batch(self, indices):
            seen.append(np.asarray(indices).copy())
            return super().batch(indices)

    return ObservedRows(data, targets)


def _adam_mu(opt_state):
    """The first-moment tree inside an optax state, wherever it sits."""
    if hasattr(opt_state, "mu"):
        return opt_state.mu
    if isinstance(opt_state, (tuple, list)):
        for s in opt_state:
            found = _adam_mu(s)
            if found is not None:
                return found
    inner = getattr(opt_state, "inner_state", None)
    return _adam_mu(inner) if inner is not None else None


class StepProbe:
    """Wraps the Trainer's compiled step; sees each step's results."""

    def __init__(self, trainer, job: dict, seconds: float, trace: bool, p0):
        import jax

        from ml_trainer_tpu.data.loader import loader_wait_snapshot

        self.jax, self.loader_wait = jax, loader_wait_snapshot
        self.seconds, self.p0 = seconds, p0
        self.inner = trainer._train_step
        self.checked = int(job["checked_steps"])
        self.warm = int(job["warmup_steps"])
        self.traced_steps = int(job.get("traced_steps", 3)) if trace else 0
        self.k = 0
        self.losses, self.first_grads, self.delta = [], None, None
        self.fence_times = {}
        self.window_steps = None
        self.t0 = self.t1 = None
        self.trace_dir = self.annotation = None
        self.wait0 = self.wait1 = None

    def _fence(self, out) -> float:
        self.jax.block_until_ready(out[1])
        return time.monotonic()

    def __call__(self, state, x, y, lr_scale):
        out = self.inner(state, x, y, lr_scale)
        self.k += 1
        k = self.k
        if k <= self.checked:
            self.losses.append(out[1])
            if k == 1:
                mu = _adam_mu(out[0].opt_state)
                if mu is None:
                    raise BenchError("no Adam first moment in the state")
                # mu after one step is (1 - b1) times the first gradient as
                # the optimizer got it; a copy, the state is donated.
                self.first_grads = reference.tree_scale(
                    mu, 1.0 / (1.0 - reference.ADAM_B1))
            if k == self.checked:
                self.delta = reference.tree_sub(out[0].params, self.p0)
                self.p0 = None
        if self.warm - 2 <= k <= self.warm:
            self.fence_times[k] = self._fence(out)
        if k == self.warm:
            step_s = self.fence_times[k] - self.fence_times[k - 1]
            self.window_steps = max(1, round(self.seconds / step_s))
            self.t0 = self.fence_times[k]
            self.wait0 = self.loader_wait()[0]
        elif self.t0 is not None and k == self.warm + self.window_steps:
            self.t1 = self._fence(out)
            self.wait1 = self.loader_wait()[0]
            if not self.traced_steps:
                raise WindowClosed()
            self._start_trace()
        elif self.t1 is not None and k == (
                self.warm + self.window_steps + self.traced_steps):
            self._fence(out)
            self._stop_trace()
            raise WindowClosed()
        return out

    def _start_trace(self):
        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        harness.start_trace(self.trace_dir)
        self.annotation = self.jax.profiler.TraceAnnotation(
            trace_reduce.WINDOW_SPAN)
        self.annotation.__enter__()

    def _stop_trace(self):
        self.annotation.__exit__(None, None, None)
        self.jax.profiler.stop_trace()

    def load_trace(self):
        if not self.trace_dir:
            return None
        try:
            return trace_reduce.load_xplane(
                trace_reduce.find_xplane(self.trace_dir))
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


def _worst_gap(got: dict, want: dict) -> float:
    """The worst leaf's gap between two norms, against the reference's norm
    of that leaf or of the median leaf, whichever is larger."""
    import jax

    g = np.asarray([float(v) for v in jax.tree.leaves(got)])
    w = np.asarray([float(v) for v in jax.tree.leaves(want)])
    return float((np.abs(g - w) / np.maximum(w, np.median(w))).max())


def compare(limits: dict, got: dict, ref: dict) -> dict:
    """The numbers a training cell can be held to, each beside its limit:
    each step's loss, the norm of the first gradient as the optimizer got
    it, the norm of the DIFFERENCE of the first gradients, and the norm of
    the parameters' change after the checked steps, the last three by the
    worst leaf.  Only the numbers the configuration's ``limits`` name are
    compared (PERF.md says which have two readings to set a limit from).
    ``got`` and ``ref`` are as a reference's ``train_steps`` returns them.
    Elements whose reference gradient is nought to rounding are left out of
    the change (``reference.moved_threshold``)."""
    import jax

    numbers = {}
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"]), start=1):
        numbers[f"loss_step{i}"] = abs(a - b) / abs(b)
    numbers["first_grad_norm"] = _worst_gap(
        reference.leaf_norms(got["first_grads"]), ref["grad_norms"])
    # Norms add in quadrature, so rounding noise hardly moves them: the one
    # number here that a lower precision moves in the first order is the
    # norm of the difference of the first gradients.
    diff = reference.leaf_norms(
        reference.tree_sub(got["first_grads"], ref["first_grads"]))
    w = np.asarray([float(v) for v in jax.tree.leaves(ref["grad_norms"])])
    d = np.asarray([float(v) for v in jax.tree.leaves(diff)])
    numbers["first_grad_diff"] = float((d / np.maximum(w, np.median(w))).max())
    thr = reference.moved_threshold(ref["first_grads"])
    numbers["param_change_norm"] = _worst_gap(
        reference.moved_change_norms(got["delta"], ref["first_grads"], thr),
        reference.moved_change_norms(ref["delta"], ref["first_grads"], thr))
    missing = set(limits) - set(numbers)
    if missing:
        raise BenchError(f"no such numbers to compare: {sorted(missing)}")
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}


def build_trainer(cell, seed: int, seconds: float, weights, seen: list,
                  model_dir: str, facts: dict):
    """The job as the configuration and the traffic file state it."""
    from ml_trainer_tpu import Trainer

    job, sizes = cell.traffic, cell.sizes()
    batch, seq = int(job["batch_size"]), int(job["seq_len"])
    # One epoch that cannot end before the window: as many steps as the
    # chip's peak could complete, and the steps before the window.
    per_step = cell.work.train_flops_per_token(sizes, seq) * batch * seq
    peak = (flops.peaks_for(facts["kind"])["bf16_flops_per_s"]
            if facts["platform"] == "tpu" else per_step * 50.0)  # rehearsal
    steps = (int(job["warmup_steps"]) + int(job.get("traced_steps", 3)) + 8
             + math.ceil(2.0 * seconds * peak / per_step))
    data, targets = token_rows(seed, steps * batch, seq, sizes["vocab"])
    val = token_rows(seed + 1, batch, seq, sizes["vocab"])
    options = dict(cell.config["program"].get("trainer_options", {}))
    trainer = Trainer(
        harness.build_model(cell.config),
        datasets=(_dataset(data, targets, seen), _dataset(*val, [])),
        epochs=1, batch_size=batch, seed=int(seed) % (1 << 31),
        model_dir=model_dir, **options)
    import jax
    import jax.numpy as jnp

    placed = jax.tree.map(jnp.copy, weights)
    trainer.state = trainer.state.replace(params=placed)
    return trainer, data, targets


def drive(cell, seed: int, seconds: float, trace: bool, facts: dict,
          t_process: float) -> dict:
    """Build the one Trainer, drive it from the seed through its first steps
    and the window, free its state.  Returns what the steps gave (as
    the reference's ``train_steps`` shapes it), the rows they were fed, the
    reference's numbers for those rows, and the probe."""
    weights = harness.make_weights(cell, seed)
    seen = []
    model_dir = tempfile.mkdtemp(prefix="bench_train_")
    try:
        trainer, data, targets = build_trainer(
            cell, seed, seconds, weights, seen, model_dir, facts)
        harness.phase("trainer_built", t_process)
        seen.clear()  # the Trainer drew one batch to shape its state
        probe = StepProbe(trainer, cell.traffic, seconds, trace, weights)
        trainer._train_step = probe
        try:
            trainer.fit()
        except WindowClosed:
            pass
        else:
            raise BenchError("the epoch ended before the window closed")
        harness.phase("window_closed", t_process)
        peak = harness.memory_peak_bytes()
        got = {"losses": [float(v) for v in probe.losses],
               "first_grads": probe.first_grads, "delta": probe.delta}
        probe.delta = probe.first_grads = probe.inner = None
        trainer.state = None
        del trainer
        gc.collect()
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)
    fed = seen[:probe.checked]
    if any(len(i) != int(cell.traffic["batch_size"]) for i in fed):
        raise BenchError("the loader fed a batch of another size")
    options = cell.config["program"].get("trainer_options", {})
    steps = dict(
        params=weights, batches=[(data[i], targets[i]) for i in fed],
        sizes=cell.sizes(), lr=float(options["lr"]),
        weight_decay=float(options.get("weight_decay", 0.0)),
        rows_per_block=int(cell.config["check"]["rows_per_block"]))
    return {"got": got, "probe": probe, "peak": peak, "steps": steps,
            "ref": cell.reference.train_steps(**steps)}


def run(cell, seed: int, seconds: float, trace: bool, t_process: float,
        allow_cpu: bool = False) -> dict:
    facts = harness.device_facts(cell.chips, allow_cpu)
    if not allow_cpu:
        harness.use_compile_cache()
    counter = harness.CompileCounter()
    harness.phase("imports_and_device", t_process)
    d = drive(cell, seed, seconds, trace, facts, t_process)
    probe = d["probe"]
    harness.forbid_compiles(counter, probe.t0, probe.t1)
    compared = compare(cell.config["limits"], d["got"], d["ref"])
    harness.phase("outputs_checked", t_process)
    batch, seq = int(cell.traffic["batch_size"]), int(cell.traffic["seq_len"])
    window_s = probe.t1 - probe.t0
    rate = probe.window_steps * batch * seq / window_s
    print(f"window: {probe.window_steps} steps in {window_s:.3f}s",
          file=sys.stderr)
    numbers = {"train_tokens_per_s": rate, "setup_s": probe.t0 - t_process}
    ctx = {"sizes": cell.sizes(),
           "window": (probe.t0, probe.t1), "trace": probe.load_trace(),
           "bytes_per_value": 2,
           "train": {"tokens_per_s": rate, "seq_len": seq, "batch": batch,
                     "window_s": window_s,
                     "traced_steps": probe.traced_steps,
                     "loader_wait_s": probe.wait1 - probe.wait0}}
    return harness.finish(cell, trace, facts, numbers, compared,
                          probe.window_steps, 0, d["peak"], ctx, allow_cpu)
