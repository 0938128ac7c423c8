#!/usr/bin/env python3
"""Where the limits of ``correct`` come from: many seeds of one cell in one
process, which pays the long set-up once.  The benchmark's own runs never
call this file; ``tests/perf_benchmark`` runs both modes at test size.

    python3 benchmark/calibrate.py serve-seeds <cell> <seconds> <n_control> <seed>... [--with <option>=<json>]
        for each seed a server and a short window at the cell's own load,
        the outputs checked and judged as a run judges them; for the first
        n_control seeds the fp8 control in the program's place, judged the
        same way; with ``--with quant_int8=true`` the program again with
        that server option switched on (its own int8 decode path)
    python3 benchmark/calibrate.py train-seeds <cell> <n_control> <seed>...
        for each seed the Trainer's first steps against the reference, every
        number the comparison can compute; for the first n_control seeds the
        fp8 control and the half-batch fault put in the program's place; on
        the first seed the compiled step's ``memory_analysis()``

Each reading is a ``CALIB`` line of JSON on standard output.
"""

import time

T_PROCESS = time.monotonic()

import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness, loadgen  # noqa: E402

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def say(**kw):
    print("CALIB " + json.dumps(kw), flush=True)
    return kw


def _serve_once(cell, seed, seconds, lowers):
    """One server on one seed: a window, then the outputs checked as they
    are served and once for each control put in the program's place."""
    from benchmark import serve_driver as drv

    t = time.monotonic()
    sv = drv.Serving(cell, seed)
    try:
        sv.warm_up()
        got = drv.measure(sv, seconds, False)
    finally:
        weights, sizes = sv.weights, sv.sizes
        sv.close()
    client = loadgen.client_stats(got["records"], got["t0"], got["t1"],
                                  cell.traffic["loop"])
    out = {"seed": seed,
           "options": cell.config["program"]["server_options"]}
    for lower in [None] + list(lowers):
        checked = drv.check_outputs(cell, weights, sizes, got["records"],
                                    seed, lower=lower)
        compared = checked.pop("compared")
        out[lower or "program"] = {
            "correct": harness.judge(compared),
            "gap_mean": compared["served_token_gap_mean"]["value"],
            "limit": compared["served_token_gap_mean"]["limit"],
            "short": compared["short_replies"]["value"], **checked}
    out.update(tokens_per_s=client["serve_tokens_per_s"],
               itl_p95_ms=client.get("itl_p95_ms"), failed=client["failed"],
               attempted=client["attempted"],
               peak=harness.memory_peak_bytes(),
               took_s=time.monotonic() - t)
    return say(**out)


def serve_seeds(name, seconds, n_control, seeds, switched_on=None,
                manifest=MANIFEST, allow_cpu=False):
    cell = harness.Cell(manifest, name)
    harness.device_facts(cell.chips, allow_cpu)
    if not allow_cpu:
        harness.use_compile_cache()
    other = None
    if switched_on:
        other = copy.copy(cell)
        other.config = copy.deepcopy(cell.config)
        other.config["program"]["server_options"].update(switched_on)
    lines = []
    for i, seed in enumerate(seeds):
        lines.append(_serve_once(cell, seed, float(seconds),
                                 ["fp8"] if i < n_control else []))
        if other is not None:
            lines.append(_serve_once(other, seed, float(seconds), []))
    return lines


def train_seeds(name, n_control, seeds, manifest=MANIFEST, allow_cpu=False):
    from benchmark import train_driver as drv

    cell = harness.Cell(manifest, name)
    facts = harness.device_facts(cell.chips, allow_cpu)
    if not allow_cpu:
        harness.use_compile_cache()
    # every number the comparison can compute, held to nothing
    limits = dict.fromkeys(
        [f"loss_step{k + 1}" for k in range(int(cell.traffic["checked_steps"]))]
        + ["first_grad_norm", "first_grad_diff", "param_change_norm"],
        float("inf"))

    class MemoryProbe(drv.StepProbe):
        """Before the first step, what the compiler says the step needs."""

        def __call__(self, state, x, y, lr_scale):
            if self.k == 0 and not lines:
                say(memory_analysis=str(self.inner.lower(
                    state, x, y, lr_scale).compile().memory_analysis()))
            return super().__call__(state, x, y, lr_scale)

    lines, real_probe = [], drv.StepProbe
    drv.StepProbe = MemoryProbe
    try:
        for i, seed in enumerate(seeds):
            t = time.monotonic()
            # A window of one step: the readings need none.
            d = drv.drive(cell, seed, 1e-9, False, facts, T_PROCESS)
            ref, steps = d["ref"], d["steps"]

            def read(other):
                return {k: v["value"]
                        for k, v in drv.compare(limits, other, ref).items()}

            out = {"seed": seed, "program": read(d["got"]),
                   "peak": d["peak"], "losses": d["got"]["losses"],
                   "ref_losses": ref["losses"]}
            if i < n_control:
                out["control_fp8"] = read(
                    cell.reference.train_steps(lower="fp8", **steps))
                out["fault_half_batch"] = read(
                    cell.reference.train_steps(half_batch=True, **steps))
            out["took_s"] = time.monotonic() - t
            lines.append(say(**out))
            del d, ref, steps
    finally:
        drv.StepProbe = real_probe
    return lines


def main(argv) -> None:
    mode, args = argv[0], argv[1:]
    switched_on = None
    if "--with" in args:
        at = args.index("--with")
        key, _, value = args[at + 1].partition("=")
        switched_on = {key: json.loads(value)}
        args = args[:at] + args[at + 2:]
    if mode == "serve-seeds":
        serve_seeds(args[0], float(args[1]), int(args[2]),
                    [int(s) for s in args[3:]], switched_on)
    elif mode == "train-seeds":
        train_seeds(args[0], int(args[1]), [int(s) for s in args[2:]])
    else:
        raise SystemExit(__doc__)


if __name__ == "__main__":
    try:
        main(sys.argv[1:])
    except harness.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr, flush=True)
        os._exit(e.code)
    sys.stdout.flush()
    os._exit(0)
