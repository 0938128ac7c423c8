"""The benchmark's own tests (CPU): the manifest and its data files, the load
generator, the trace reduction, the plain reference against the program, a
rehearsal of every driver at tiny sizes, and the runs that must come out as
NOT correct (the control, and the timed path broken underneath).

The tiny cells are test presets (``tiny/``), one file a rehearsal under
``tiny/cells/``; ``conftest.py`` reads them, builds their manifest (the
root's own with those names swapped in) and hands out ``tiny``,
``run_tiny``, ``rehearse`` and the rehearsal's cases, so that a new cell or a
new architecture brings a file and no edit here.  No number a CPU run prints
here is a device metric, and the harness prints none from a CPU.
"""

import asyncio
import gzip
import json
import os
import re
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from benchmark import harness, loadgen, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "benchmark")
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load(path):
    with open(path) as fp:
        return json.load(fp)


# ------------------------------------------------------------ the manifest
def test_manifest_keeps_to_the_contract_and_every_file_loads():
    b = _load(MANIFEST)
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    cells = {w["name"] for w in b["workloads"]}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", [])) <= cells
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for c in b["configs"]:
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        cfg = _load(os.path.join(ROOT, c["file"]))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
        assert cfg["program"]["entry"] in ("serve", "train")
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        cell = harness.Cell(MANIFEST, w["name"])
        # each configuration's OWN reference reads its keys
        assert {"vocab", "positions"} <= set(cell.sizes())
        reported = {m["name"] for m in cell.metrics("end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layer = cell.metrics("per_layer")
        assert layer
        for m in layer:
            spec = cell.layer_metric(m["name"])
            assert spec["layer"] == m["layer"]
            assert spec["source"] == m["source"]
            assert m["moves"] in reported, (w["name"], m["name"])
            assert os.path.exists(os.path.join(
                BENCH, "readers", spec["reader"] + ".py"))
    for m in b["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


# ------------------------------------------------------ the load generator
@pytest.mark.parametrize("mix", [
    os.path.join(BENCH, "traffic", "batch-decode.json"),
    os.path.join(HERE, "tiny", "traffic", "open-tiny.json")], ids=os.path.basename)
def test_schedule_same_seed_same_bytes_and_every_seed_the_same_work(mix):
    traffic = _load(mix)
    n = traffic["block"]

    def first(seed, k):
        it = loadgen.iter_schedule(traffic, 50257, seed)
        return [next(it) for _ in range(k)]

    a, b = first(2**31 + 11, 2 * n), first(2**31 + 11, 2 * n)
    assert json.dumps(a) == json.dumps(b)
    other = first(5, 2 * n)
    assert json.dumps(a) != json.dumps(other)
    for reqs in (a, other):
        for r in reqs:
            p, o = len(r["prompt"]), r["max_new_tokens"]
            assert traffic["prompt_len"]["min"] <= p <= traffic["prompt_len"]["max"]
            assert 1 <= o <= traffic["output_len"]["max"]
            assert p + o <= traffic["max_total"] <= 1024
            assert 0 <= min(r["prompt"]) and max(r["prompt"]) < 50257

    def work(reqs):
        return sorted((len(r["prompt"]), r["max_new_tokens"]) for r in reqs)

    # Each block is the same multiset in another order, whatever the seed.
    assert work(a[:n]) == work(a[n:]) == work(other[:n])
    assert [len(r["prompt"]) for r in a[:n]] != [len(r["prompt"]) for r in other[:n]]
    lens = np.asarray([len(r["prompt"]) for r in a[:n]])
    assert np.median(lens) == pytest.approx(
        traffic["prompt_len"]["median"], rel=0.1)
    assert lens.max() > 2.5 * np.median(lens)  # a heavy tail, not uniform


def test_batch_decode_lengths_have_the_means_of_their_source():
    """ShareGPT as vLLM's benchmark replays it (Kwon et al. 2023, figure
    11): mean input 161.31 tokens, mean output 337.99, after every clip."""
    traffic = _load(os.path.join(BENCH, "traffic", "batch-decode.json"))
    assert "arXiv:2309.06180" in traffic["source"]
    block = loadgen.base_block(traffic)
    assert block["prompt_len"].mean() == pytest.approx(161.31, rel=0.01)
    assert block["output_len"].mean() == pytest.approx(337.99, rel=0.01)


def test_open_loop_arrivals_are_poisson_from_the_seed():
    traffic = _load(os.path.join(HERE, "tiny", "traffic", "open-tiny.json"))
    assert traffic["arrivals"] == "poisson"

    def dues(seed, k=4000):
        it = loadgen.iter_schedule(traffic, 100, seed)
        return np.asarray([next(it)["due"] for _ in range(k)])

    a = dues(2**31 + 3)
    assert np.array_equal(a, dues(2**31 + 3))
    gaps = np.diff(a, prepend=0.0)
    assert gaps.min() > 0
    assert gaps.mean() == pytest.approx(1 / traffic["rate_rps"], rel=0.05)
    # exponential gaps: as wide as their mean, bursts and lulls included
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.1)
    # arrivals in a second vary as a Poisson count does, seed by seed too
    per_s = np.bincount(a.astype(int))[:-1]
    assert per_s.var() == pytest.approx(per_s.mean(), rel=0.3)
    assert not np.array_equal(a, dues(5))
    with pytest.raises(ValueError):
        next(loadgen.iter_schedule({**traffic, "arrivals": "even"}, 100, 1))


def test_latency_runs_from_the_due_time_and_rates_over_the_whole_window():
    t0, t1 = 100.0, 110.0

    def rec(due, sent, times, status="ok"):
        return {"id": 0, "prompt_len": 8, "max_new_tokens": len(times),
                "due": due, "sent": sent, "status": status, "error": None,
                "tokens": [1] * len(times), "times": times}

    records = [
        rec(101.0, 101.5, [102.0, 102.1, 102.3]),      # sent late: lag 500
        rec(105.0, 105.0, [105.2, 109.9, 110.5]),      # last token outside
        rec(99.0, 99.0, [99.5, 100.5]),                # due before the window
        rec(109.0, 109.0, [], status="refused"),       # a refusal misses
    ]
    s = loadgen.client_stats(records, t0, t1, "open")
    assert s["attempted"] == 3 and s["failed"] == 1
    assert s["tokens"] == 6 and s["serve_tokens_per_s"] == pytest.approx(0.6)
    assert s["ttft_p50_ms"] == pytest.approx(1000.0)    # from DUE, not sent
    assert s["ttft_p95_ms"] == loadgen.MISS_MS
    assert s["send_lag_p95_ms"] == pytest.approx(500.0)
    # gaps: only between two tokens that both arrived inside the window
    assert s["gaps"] == 3 and s["itl_p95_ms"] == pytest.approx(4700.0)
    closed = loadgen.client_stats(records, t0, t1, "closed")
    assert closed["ttft_p50_ms"] == pytest.approx(500.0)  # from the send


class _StallingServer:
    """Speaks ``/v1/stream``: a token every 5 ms, and one stall during which
    every open stream waits (what a long prefill does to decoding slots)."""

    def __init__(self, stall_at: float, stall_s: float):
        outer = self
        self.stall_from = time.monotonic() + stall_at
        self.stall_to = self.stall_from + stall_s

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(n))
                self.send_response(200)
                self.send_header("Content-Type", "application/x-ndjson")
                self.end_headers()

                def line(obj):
                    self.wfile.write(json.dumps(obj).encode() + b"\n")
                    self.wfile.flush()

                try:
                    line({"status": "accepted"})
                    for i in range(body["max_new_tokens"]):
                        now = time.monotonic()
                        if outer.stall_from <= now < outer.stall_to:
                            time.sleep(outer.stall_to - now)
                        time.sleep(0.005)
                        line({"t": i})
                    line({"done": {"state": "done"}})
                except OSError:
                    pass

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.httpd.daemon_threads = True
        threading.Thread(target=self.httpd.serve_forever, daemon=True).start()

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_a_stalled_server_shows_in_ttft_and_in_the_whole_window_rate():
    traffic = _load(os.path.join(HERE, "tiny", "traffic", "open-tiny.json"))
    traffic["rate_rps"], traffic["lead_in_s"] = 40.0, 0.0

    def drive(stall_s):
        server = _StallingServer(stall_at=0.9, stall_s=stall_s)
        try:
            start = time.monotonic() + 0.1
            plan = {"host": "127.0.0.1", "port": server.httpd.server_port,
                    "vocab": 100, "seed": 3, "loop": "open",
                    "traffic": traffic, "start_at": start,
                    "end_at": start + 1.5, "first_token_wait_s": 5.0,
                    "clients": 0}
            records = asyncio.run(loadgen._run(plan))
        finally:
            server.close()
        return loadgen.client_stats(records, start, start + 1.5, "open")

    calm, stalled = drive(0.0), drive(0.9)
    assert calm["failed"] == stalled["failed"] == 0
    assert calm["attempted"] == stalled["attempted"] > 30   # the same schedule
    # arrivals during the stall wait for its end (the limits leave room for
    # a test machine whose other workers hold the cores)
    assert stalled["ttft_p95_ms"] > calm["ttft_p95_ms"] + 300
    # every token over the whole window: the stall's last 0.7 s of 1.5 s shows
    assert stalled["serve_tokens_per_s"] < 0.85 * calm["serve_tokens_per_s"]
    # the generator itself kept time: the wait is the server's, not its own
    assert stalled["send_lag_p95_ms"] < stalled["ttft_p95_ms"] - 300


# ------------------------------------------------------ the trace reduction
def test_trace_reduction_arithmetic():
    us = 1000
    trace = {
        "window": [0, 1000 * us],
        "devices": {"/device:TPU:0": [
            ["fusion.1", 100 * us, 100 * us],
            ["while.2", 300 * us, 300 * us],           # a loop ...
            ["flash_fwd", 320 * us, 100 * us],         # ... and its body
            ["flash_fwd", 450 * us, 100 * us],
            ["fusion.1", 590 * us, 30 * us],           # overlaps the loop's end
            ["copy.3", 900 * us, 200 * us],            # runs past the window
        ]},
        "host": [["serve_prefill", 200 * us, 90 * us],
                 ["serve_decode", 610 * us, 280 * us]],
    }
    busy = trace_reduce.busy_intervals(
        trace["devices"]["/device:TPU:0"], *trace["window"])
    assert busy == [[100 * us, 200 * us], [300 * us, 620 * us],
                    [900 * us, 1000 * us]]
    red = trace_reduce.reduce(trace)
    assert red["window_s"] == pytest.approx(1e-3)
    assert red["busy_s"] == pytest.approx(520e-6)
    ops = dict(red["device_ops"])
    assert ops["flash_fwd"] == pytest.approx(200e-6)
    assert ops["while"] == pytest.approx(70e-6)        # self time only
    assert ops["fusion"] == pytest.approx(130e-6)      # instances added up
    assert "copy" not in ops                           # not whole inside
    gaps = dict(red["idle_gaps"])
    assert gaps == {"none": pytest.approx(100e-6),
                    "serve_prefill": pytest.approx(100e-6),
                    "serve_decode": pytest.approx(280e-6)}
    assert sum(gaps.values()) + red["busy_s"] == pytest.approx(red["window_s"])
    secs, calls = trace_reduce.op_seconds(trace, "flash")
    assert (secs, calls) == (pytest.approx(200e-6), 2)
    assert trace_reduce.op_seconds(trace, "paged")[1] == 0


def test_trace_reduction_on_the_recorded_trace():
    """A piece of a real trace of the chip (fixtures/README), in normal
    form: the reduction gives what was worked out by hand when it was cut."""
    path = os.path.join(BENCH, "fixtures", "serve_trace_v5e.json.gz")
    with gzip.open(path, "rt") as fp:
        fixture = json.load(fp)
    trace, want = fixture["trace"], fixture["expect"]
    red = trace_reduce.reduce(trace)
    assert red["window_s"] == pytest.approx(want["window_s"])
    assert red["busy_s"] == pytest.approx(want["busy_s"], rel=1e-6)
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle + red["busy_s"] == pytest.approx(red["window_s"], rel=1e-6)
    assert red["device_ops"][0][0] == want["top_op"]
    assert {g[0] for g in red["idle_gaps"]} >= set(want["gap_labels"])
    secs, calls = trace_reduce.op_seconds(trace, want["pattern"])
    assert calls == want["pattern_calls"]
    assert secs == pytest.approx(want["pattern_s"], rel=1e-6)


# ------------------------------------------- the reference and the program
def test_reference_agrees_with_the_program_through_prefill_and_cache(tiny):
    import jax
    import jax.numpy as jnp

    from ml_trainer_tpu.models import get_model

    cell = harness.Cell(tiny, "tiny.closed")
    reference, sizes = cell.reference, cell.sizes()
    weights = harness.make_weights(cell, 2**31 + 5)
    model = get_model("gpt2_tiny")
    shapes = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)},
                           jnp.zeros((1, 8), jnp.int32), train=False))
    assert (jax.tree.map(lambda s: s.shape, shapes["params"])
            == jax.tree.map(lambda w: w.shape, weights))
    ids = np.random.default_rng(0).integers(0, sizes["vocab"], size=(2, 24))
    want = np.asarray(reference.logits(weights, jnp.asarray(ids),
                                       sizes["heads"]))
    got = np.asarray(model.apply({"params": weights}, jnp.asarray(ids),
                                 train=False))
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()
    # Prefill of 16 tokens, then 8 single-token steps through the cache.
    dm = model.clone(decode=True)
    cache = dm.init({"params": jax.random.PRNGKey(0)},
                    jnp.zeros((2, 1), jnp.int32), train=False)["cache"]
    cache = jax.tree.map(jnp.zeros_like, cache)
    out, mut = dm.apply({"params": weights, "cache": cache},
                        jnp.asarray(ids[:, :16]), train=False,
                        mutable=["cache"])
    rows = [np.asarray(out)]
    for t in range(16, 24):
        out, mut = dm.apply({"params": weights, "cache": mut["cache"]},
                            jnp.asarray(ids[:, t:t + 1]), train=False,
                            mutable=["cache"])
        rows.append(np.asarray(out))
    got = np.concatenate(rows, axis=1)
    assert np.abs(got - want).max() < 2e-4 * np.abs(want).max()
    # The serving comparison reads gaps of zero for the reference's own
    # greedy tokens, and a plain gap for a token that is not the best.
    seq = list(ids[0, :16])
    for _ in range(8):
        row = reference.logits(weights, jnp.asarray([seq]), sizes["heads"])
        seq.append(int(np.asarray(row)[0, -1].argmax()))
    served = np.asarray(seq[16:])
    short = {**sizes, "positions": 64}
    gaps = reference.served_token_gaps(weights, short, ids[0, :16], served)
    assert gaps.shape == (8,) and gaps.max() < 1e-4
    served[3] = (served[3] + 1) % sizes["vocab"]
    bad = reference.served_token_gaps(weights, short, ids[0, :16], served)
    assert bad[3] > 0.01 and bad[:3].max() < 1e-4


def test_every_driver_rehearses_end_to_end_on_the_cpu(rehearse, tiny_case):
    """Every file under ``tiny/cells/``, untraced and traced: the end-to-end
    metrics the manifest lists for the cell, or the per-layer ones its file
    says a CPU can read, and never a device metric."""
    name, trace = tiny_case
    rehearse(name, trace, seed=2**31 + 17)


def test_no_accelerator_no_result():
    with pytest.raises(harness.BenchError) as e:
        harness.device_facts(1)
    assert e.value.code == harness.EXIT_NO_CHIP


# ------------------------------------------------- what has to fail correct
def test_cache_fill_counts_live_positions_over_the_reserved_pool():
    from benchmark.readers import cache_fill

    def rec(prompt_len, times):
        return {"prompt_len": prompt_len, "times": times}

    ctx = {"window": (10.0, 20.0), "slots": 2, "sizes": {"positions": 100},
           "records": [rec(40, [12.0, 14.0, 16.0]),   # 41 for 2 s, 42 for 2 s
                       rec(10, [8.0, 12.0]),          # 11 from 10.0 to 12.0
                       rec(90, [19.0, 25.0]),         # 91 for the last second
                       rec(50, [21.0, 22.0])]}        # after the window
    held = 41 * 2 + 42 * 2 + 11 * 2 + 91 * 1
    assert cache_fill.read(ctx) == pytest.approx(100 * held / (10 * 200))
    assert cache_fill.read({**ctx, "records": []}) is None


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, tiny, run_tiny):
    from ml_trainer_tpu.serving.scheduler import Request

    real = Request.push_token

    def altered(self, token):
        # every seventh token of a request comes out one id too high
        n = len(self.tokens)
        return real(self, (token + 1) % 1024 if n % 7 == 3 else token)

    monkeypatch.setattr(Request, "push_token", altered)
    line = run_tiny(tiny, "tiny.closed", seed=23)
    assert line["correct"] is False
    gap = line["compared"]["served_token_gap_mean"]
    assert gap["value"] > 10 * gap["limit"]


def _break_train_step(monkeypatch, breaker):
    from ml_trainer_tpu.trainer import Trainer

    real = Trainer._make_train_step

    def broken(self):
        return breaker(real(self))

    monkeypatch.setattr(Trainer, "_make_train_step", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_train_step_is_not_correct(monkeypatch, tiny, run_tiny,
                                            fault):
    def state_unchanged(step):
        def f(state, x, y, lr_scale):
            return (state,) + tuple(step(state, x, y, lr_scale)[1:])
        return f

    def half_batch(step):
        def f(state, x, y, lr_scale):
            n = x.shape[0] // 2
            return step(state, x[:n], y[:n], lr_scale)
        return f

    _break_train_step(monkeypatch, locals()[fault])
    line = run_tiny(tiny, "tiny.train", seed=31)
    assert line["correct"] is False
    over = {k for k, c in line["compared"].items() if c["value"] > c["limit"]}
    if fault == "state_unchanged":
        assert line["compared"]["param_change_norm"]["value"] == pytest.approx(1.0)
        assert "param_change_norm" in over
    else:
        assert "first_grad_norm" in over


def test_the_fp8_control_fails_a_training_number_at_test_size(tiny):
    """The reference put in the program's place, its blocks' products in
    float8: it has to fail one of the training cell's numbers."""
    from benchmark import train_driver

    cell = harness.Cell(tiny, "tiny.train")
    reference, sizes = cell.reference, cell.sizes()
    for seed in (1, 2, 3):
        weights = harness.make_weights(cell, seed)
        data, targets = train_driver.token_rows(seed, 24, 128, sizes["vocab"])
        batches = [(data[i:i + 8], targets[i:i + 8]) for i in (0, 8, 16)]
        kw = dict(sizes=sizes, lr=1e-4, weight_decay=0.0, rows_per_block=4)
        ref = reference.train_steps(weights, batches, **kw)
        same = train_driver.compare(cell.config["limits"], ref, ref)
        assert harness.judge(same)
        control = train_driver.compare(
            cell.config["limits"],
            reference.train_steps(weights, batches, lower="fp8", **kw), ref)
        assert not harness.judge(control), control


def test_the_fp8_control_in_the_programs_place_is_not_correct(tiny):
    """Serving's control at test size, free of any clock: the program's own
    ``generate()`` answers a block of the schedule; its tokens are checked
    and judged as a run's are and come out correct, and the fp8 control put
    in the program's place (``lower``) does not.  The limit is this size's
    own: over 1,536 tokens the program reads 0 to 1.4e-7 and the control
    8.8e-6 to 3.0e-5 (CPU, five seeds; a sixth flipped no token at all, so
    the seeds are fixed)."""
    import jax.numpy as jnp

    from benchmark import serve_driver
    from ml_trainer_tpu.generate import generate

    cell = harness.Cell(tiny, "tiny.closed")
    n, p_len, o_len = 32, 16, 48

    def fixed(v):
        return {"dist": "fixed", "value": v, "min": v, "max": v}

    cell.traffic = {**cell.traffic, "block": n, "prompt_len": fixed(p_len),
                    "output_len": fixed(o_len)}
    cell.config = {**cell.config, "check": {"requests": n},
                   "limits": {"served_token_gap_mean": 3e-6}}
    sizes = cell.sizes()
    model = harness.build_model(cell.config)
    for seed in (1, 2, 2**31 + 29):
        weights = harness.make_weights(cell, seed)
        schedule = loadgen.iter_schedule(cell.traffic, sizes["vocab"], seed)
        reqs = [next(schedule) for _ in range(n)]
        out = np.asarray(generate(
            model, {"params": weights},
            jnp.asarray([r["prompt"] for r in reqs], jnp.int32), o_len))
        records = [{"id": r["id"], "prompt_len": p_len, "status": "ok",
                    "max_new_tokens": o_len, "tokens": out[i, p_len:].tolist()}
                   for i, r in enumerate(reqs)]
        for lower, expect in ((None, True), ("fp8", False)):
            checked = serve_driver.check_outputs(
                cell, weights, sizes, records, seed, lower=lower)
            assert checked["tokens_checked"] == n * o_len
            assert harness.judge(checked["compared"]) is expect, (
                seed, lower, checked)


def test_calibration_checks_each_seed_as_a_run_would(tiny, capsys):
    """``calibrate.py serve-seeds`` at test size: a server and a window for
    each seed, the outputs checked and judged; for the first ``n_control``
    seeds the fp8 control over the same sample; a server option switched on
    is a second server on the same seed."""
    from benchmark import calibrate

    lines = calibrate.serve_seeds(
        "tiny.closed", 1.0, 1, [2**31 + 29, 7], {"max_batch": 2},
        manifest=tiny, allow_cpu=True)
    plain, switched = lines[0::2], lines[1::2]
    assert [ln["seed"] for ln in plain] == [2**31 + 29, 7]
    assert all(ln["program"]["correct"] and ln["failed"] == 0 for ln in lines)
    assert all(ln["program"]["tokens_checked"] > 0 for ln in lines)
    fp8 = plain[0]["fp8"]
    assert fp8["tokens_checked"] == plain[0]["program"]["tokens_checked"]
    assert fp8["correct"] == (fp8["gap_mean"] <= fp8["limit"])
    assert "fp8" not in plain[1]                   # n_control seeds only
    assert all(ln["options"]["max_batch"] == 2 for ln in switched)
    assert all(ln["options"]["max_batch"] == 4 for ln in plain)
    assert capsys.readouterr().out.count("CALIB ") == 4


def test_calibration_reads_every_training_number_and_the_step_memory(
        tiny, capsys):
    from benchmark import calibrate

    lines = calibrate.train_seeds("tiny.train", 1, [5, 2**31 + 6],
                                  manifest=tiny, allow_cpu=True)
    names = {"loss_step1", "loss_step2", "loss_step3", "first_grad_norm",
             "first_grad_diff", "param_change_norm"}
    limits = harness.Cell(tiny, "tiny.train").config["limits"]
    for ln in lines:
        assert set(ln["program"]) == names
        assert all(ln["program"][k] <= limits[k] for k in limits)
    first, second = lines
    assert (first["control_fp8"]["first_grad_diff"]
            > 3 * first["program"]["first_grad_diff"])
    assert first["fault_half_batch"]["first_grad_norm"] > limits["first_grad_norm"]
    assert "control_fp8" not in second
    out = capsys.readouterr().out
    assert out.count("memory_analysis") == 1       # the first seed's step
