"""The benchmark's side of ``brumby`` (Brumby-14B-Base, power retention), on
the CPU at the tiny size of ``tiny/configs/brumby-tiny-serve.json``: the
plain reference (the layer's QUADRATIC form) against the program through
prefill and state, the runs that must come out as NOT correct (the fp8
control, a token altered where it is produced), the work module's counts
against a hand count, and what the configuration's file has to say.  The
rehearsal of the cell itself is ``tiny/cells/brumby-14b-l8.reason-4k.json``,
run by ``test_benchmark_cells.py`` with every other.
"""

import importlib
import json
import os

import numpy as np
import pytest

from benchmark import harness, loadgen
from benchmark.readers import span_arg_stat, step_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CELL, TINY = "brumby-14b-l8.reason-4k", "tiny.brumby-closed"
NEW_METRICS = ("retention_decode_step_roofline", "retention_state_share_pct",
               "retention_gate_mean", "retention_norm_min",
               "retention_state_step_roofline")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
# the catalog row's ``config`` (model-configs guide), for where the guide
# is not installed
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def test_reference_agrees_with_the_program_through_prefill_and_state(tiny):
    """The weights the reference makes ARE the program's tree (names, shapes
    and precisions); the program in float32 reads the reference's logits
    through a prefill longer than a chunk (the chunked form against the
    reference's quadratic one) and decode steps through the state: float32
    sums in another order, 2e-5 of the largest logit; the program in the
    stated bfloat16 reads a hundred times that."""
    import jax
    import jax.numpy as jnp

    cell = harness.Cell(tiny, TINY)
    reference, sizes = cell.reference, cell.sizes()
    assert (sizes["layers"], sizes["heads"], sizes["kv_heads"]) == (4, 10, 2)
    weights = harness.make_weights(cell, 2**31 + 5)
    stated = harness.build_model(cell.config)
    shapes = jax.eval_shape(
        lambda: stated.init({"params": jax.random.PRNGKey(0)},
                            jnp.zeros((1, 8), jnp.int32), train=False))
    assert (jax.tree.map(lambda s: (s.shape, s.dtype), shapes["params"])
            == jax.tree.map(lambda w: (w.shape, w.dtype), weights))
    assert {str(w.dtype) for w in jax.tree.leaves(weights)} == {
        "bfloat16", "float32"}
    attn = weights["block0"]["attn"]
    assert attn["gate"]["kernel"].shape == (64, 2)
    assert attn["q_norm"]["scale"].dtype == jnp.float32
    # nothing is multiplied by an exact 0 or 1
    assert all(np.all((np.asarray(w, np.float32) != 1)
                      & (np.asarray(w, np.float32) != 0))
               for w in jax.tree.leaves(weights["block0"]))

    ids = np.random.default_rng(0).integers(0, sizes["vocab"], size=(2, 32))
    want = np.asarray(reference.logits(weights, ids, sizes))

    def through_the_state(model):
        dm = model.clone(decode=True)
        cache = jax.tree.map(jnp.zeros_like, dm.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((2, 1), jnp.int32),
            train=False)["cache"])
        assert cache["block0"]["attn"]["state"].shape == (2, 2, 16, 144)
        assert cache["block0"]["attn"]["state"].dtype == jnp.float32
        assert cache["block0"]["attn"]["norm"].shape == (2, 2, 144)
        step = jax.jit(lambda cache, tok: dm.apply(
            {"params": weights, "cache": cache}, tok, train=False,
            mutable=["cache", "step_counters"]))
        out, mut = step(cache, jnp.asarray(ids[:, :12]))
        rows = [np.asarray(out)]
        for t in range(12, 32):
            out, mut = step(mut["cache"], jnp.asarray(ids[:, t:t + 1]))
            rows.append(np.asarray(out))
        return np.concatenate(rows, axis=1)

    exact = through_the_state(stated.clone(dtype=jnp.float32))
    assert np.abs(exact - want).max() < 2e-5 * np.abs(want).max()
    rounded = through_the_state(stated)
    assert np.abs(rounded - want).max() > 2e-3 * np.abs(want).max()
    # The serving comparison reads gaps of zero for the reference's own
    # greedy tokens, and a plain gap for a token that is not the best.
    seq = list(ids[0, :12])
    for _ in range(8):
        row = reference.logits(
            weights, np.asarray([seq + [0] * (20 - len(seq))]), sizes)
        seq.append(int(np.asarray(row)[0, len(seq) - 1].argmax()))
    served = np.asarray(seq[12:])
    gaps = reference.served_token_gaps(weights, sizes, ids[0, :12], served)
    assert gaps.shape == (8,) and gaps.max() < 1e-5
    served[3] = (served[3] + 1) % sizes["vocab"]
    bad = reference.served_token_gaps(weights, sizes, ids[0, :12], served)
    assert bad[3] > 0.01 and bad[:3].max() < 1e-5


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "references",
                           "brumby.py")) as fp:
        source = fp.read()
    assert "import ml_trainer_tpu" not in source
    assert "from ml_trainer_tpu" not in source
    assert "phi(" not in source.split('"""', 2)[2]     # no state, no phi


def test_the_fp8_control_in_the_programs_place_is_not_correct(tiny):
    """Free of any clock: the program's own ``generate()`` answers a block of
    the schedule in the stated bfloat16; its tokens are checked and judged as
    a run's are and come out correct, and the fp8 control put in the
    program's place (``lower``: the gate's product rounded too) does not.
    The limit is this size's own, between the two readings over 768 tokens a
    seed (CPU, three seeds; ``limits_note`` of the tiny configuration gives
    them, and the seed whose tiny weights make too few near-ties to tell)."""
    import jax.numpy as jnp

    from benchmark import serve_driver
    from ml_trainer_tpu.generate import generate

    cell = harness.Cell(tiny, TINY)
    n, p_len, o_len = 16, 12, 48

    def fixed(v):
        return {"dist": "fixed", "value": v, "min": v, "max": v}

    cell.traffic = {**cell.traffic, "block": n, "prompt_len": fixed(p_len),
                    "output_len": fixed(o_len)}
    cell.config = {**cell.config, "check": {"requests": n},
                   "limits": {"served_token_gap_mean": 4e-5}}
    sizes = cell.sizes()
    model = harness.build_model(cell.config)
    for seed in (1, 2, 3):
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


def test_a_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch, tiny, run_tiny):
    from ml_trainer_tpu.serving.scheduler import Request

    real = Request.push_token

    def altered(self, token):
        # every seventh token of a request comes out one id too high
        n = len(self.tokens)
        return real(self, (token + 1) % 256 if n % 7 == 3 else token)

    monkeypatch.setattr(Request, "push_token", altered)
    line = run_tiny(tiny, TINY, seed=23)
    assert line["correct"] is False
    gap = line["compared"]["served_token_gap_mean"]
    assert gap["value"] > 10 * gap["limit"]


def test_the_rehearsal_reads_the_retentions_counters(rehearse):
    """The cell end to end on the CPU, traced: ``correct``, and the two
    counters of the decode step in the line (a gate is a sigmoid's value; a
    divisor is a sum of squares)."""
    line = rehearse(TINY, True, 2**31 + 41)
    assert 0.2 < line["metrics"]["retention_gate_mean"]["value"] < 0.8
    assert line["metrics"]["retention_norm_min"]["value"] > 0


# ------------------------------------------------------------ work counts
def test_work_counts_against_a_hand_count_at_the_tiny_sizes(tiny):
    cell = harness.Cell(tiny, TINY)
    work, s = cell.work, cell.sizes()
    assert work.state_rows(s) == 136                      # 16 x 17 / 2
    # q 64 x 160, k and v 64 x 32, the gate 64 x 2, o 160 x 64, 3 x 64 x 96
    params = 2 * 64 * 160 + 2 * 64 * 32 + 64 * 2 + 3 * 64 * 96    # 43,136
    assert work.layer_params(s) == params
    # 10 heads read S and z, 2 heads gate and add to them, 12 phis
    core = 10 * 2 * 136 * 17 + 2 * 3 * 136 * 17 + 12 * 2 * 136    # 63,376
    assert work.retention_step_flops(s) == core
    head = 2 * 64 * 256
    token = 4 * (2 * params + core)
    # the same at every context: the state does not grow
    assert work.decode_flops(s, 0) == work.decode_flops(s, 4000) == (
        head + token)
    # a prompt of 11: 66 pairs of 4 x 16 + 3 a query head, then the state
    # built once from 11 tokens; the recurrence would cost 11 x core
    quadratic = 10 * 67 * 66 + 2 * 11 * (2 * 136 * 17 + 2 * 136)
    assert quadratic < 11 * core
    assert work.retention_prompt_flops(s, 11) == quadratic
    assert work.prefill_flops(s, 11) == head + 4 * (
        2 * 11 * params + quadratic)
    # a long enough prompt is cheaper a token at a time
    assert work.retention_prompt_flops(s, 4000) == 4000 * core
    with pytest.raises(NotImplementedError, match="served only"):
        work.train_flops_per_token(s, 128)
    # bytes of a step: every leaf of the tree but the embedding's rows
    import jax

    weights = harness.make_weights(cell, 3)
    whole = sum(w.size * w.dtype.itemsize for w in jax.tree.leaves(weights))
    assert work.step_weight_bytes(s) == whole - 256 * 64 * 2
    # a slot's state: 4 layers of 2 x 136 x (16 + 1) float32 by count, in
    # nine rows of 16 lanes as laid out
    assert work.state_bytes(s) == 4 * 2 * 136 * 17 * 4
    assert work.state_bytes(s, as_laid_out=True) == 4 * 2 * 144 * 17 * 4
    # one step a row: two rows, three steps in the window
    ctx = {"sizes": s, "slots": 4, "window": (10.0, 20.0),
           "samples": {"step_secs": [0.5, 0.25, 0.75]},
           "records": [{"prompt_len": 20, "times": [9.0, 11.0, 25.0]},
                       {"prompt_len": 3, "times": [12.0, 13.0]}]}
    ops, moved = work.decode_step_work(ctx)
    assert ops == pytest.approx(2 * work.decode_flops(s, 0) / 3)
    # every slot's state read and written, whatever the rows in flight
    assert moved == pytest.approx(
        work.step_weight_bytes(s) + 2 * 4 * work.state_bytes(s)
        + 2 * 64 * 2 / 3)
    # the reader: silent without a device trace, else least over mean
    ctx.update(work=work, peaks={"bf16_flops_per_s": 1e9,
                                 "hbm_bytes_per_s": 1e6})
    assert step_roofline.read(ctx, work="decode_step_work") is None
    ctx["trace_reduced"] = {"busy_s": 1.0}
    assert step_roofline.read(ctx, work="decode_step_work") == pytest.approx(
        100.0 * max(ops / 1e9, moved / 1e6) / 0.5)
    assert ctx["notes"]["decode_step_work_bound"] == "memory"
    # the kernel's calls in a traced slice: one a layer a step, every slot's
    # state read and written whatever the rows in flight
    kernel = "retention_state_step.3|tpu_custom_call"
    ctx["trace"] = {"window": (0, 100), "devices": {0: [
        (kernel, 1, 10), ("fusion.1", 12, 5), (kernel, 20, 10),
        (kernel, 95, 10)]}}                    # the last one ends outside
    ops, moved = work.retention_state_step(ctx)
    assert ops == 2 * 4 * core
    assert moved == 2 * 2 * 4 * (2 * 136 * 17 * 4)
    from benchmark.readers import trace_roofline
    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics",
        "retention_state_step_roofline.json"))
    assert spec["reader"] == "trace_roofline"
    assert trace_roofline.read(ctx, **spec["params"]) == pytest.approx(
        100.0 * max(ops / 1e9, moved / 1e6) / 20e-9)
    ctx["trace"]["devices"] = {0: [("fusion.1", 12, 5)]}
    assert trace_roofline.read(ctx, **spec["params"]) is None    # silent


def test_the_counters_are_read_from_the_fence_spans():
    import time

    from ml_trainer_tpu.telemetry import spans

    name = "test_brumby.fence"
    t0 = time.monotonic()
    with spans.span(name, gate_mean=0.25, norm_min=4.0):
        pass
    with spans.span(name, gate_mean=0.75, norm_min=2.0):
        pass
    with spans.span(name, expert_rows=3.0):           # another model's fence
        pass
    ctx = {"window": (t0, time.monotonic()), "sizes": {}}
    for metric, want in (("retention_gate_mean", 0.5),
                         ("retention_norm_min", 3.0)):
        spec = harness.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", metric + ".json"))
        assert spec["reader"] == "span_arg_stat"
        assert spec["params"]["names"] == ["serve_decode.fence"]
        assert span_arg_stat.read(
            ctx, **{**spec["params"], "names": [name]}) == pytest.approx(want)


# ------------------------------------------------------ the configuration
def test_the_configuration_states_its_cut_and_the_program_runs_its_widths():
    b = harness.load_json(MANIFEST)
    entry = {c["name"]: c for c in b["configs"]}["brumby-14b-l8"]
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    published = PUBLISHED
    if os.path.exists(CATALOG):
        with open(CATALOG) as fp:
            rows = [json.loads(line) for line in fp]
        (row,) = [r for r in rows if r["name"] == cfg["catalog_name"]]
        assert row["config"] == PUBLISHED
        assert cfg["source"] == entry["source"] == row["source_url"]
    assert cfg["catalog_name"] == "Brumby-14B-Base"
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "max_position_embeddings"]
    # every published key as published, but the two that are cut
    assert {k: cfg[k] for k in published if k not in cfg["reduced"]} == {
        k: v for k, v in published.items() if k not in cfg["reduced"]}
    assert cfg["published"] == {k: published[k] for k in cfg["reduced"]}
    assert [cfg[k] for k in cfg["reduced"]] == [8, 4096]
    for key in ("deployment", "assumed", "precision", "limits", "memory",
                "reduced_note", "described_in"):
        assert cfg[key], key
    for key in ("power", "gate", "scale", "normaliser", "qk_norm_and_rope",
                "float32", "weights", "block"):
        assert cfg["assumed"][key], key
    assert "arXiv:2507.04239" in cfg["described_in"]
    assert cfg["precision"]["stated"] == "bfloat16"
    assert cfg["program"]["model_options"] == {
        "dtype": "bfloat16", "num_layers": 8, "max_len": 4096}
    assert cfg["program"]["server_options"] == {
        "max_batch": 16, "max_queue": 64, "watchdog_timeout": 900.0}
    # the program's model at this configuration runs every published width
    model = harness.build_model(cfg)
    assert (model.embed_dim, model.num_heads, model.num_kv_heads,
            model.head_dim, model.dense_dim, model.vocab_size,
            model.rope_theta, model.eps) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["intermediate_size"], cfg["vocab_size"], cfg["rope_theta"],
        cfg["rms_norm_eps"]) == (
        5120, 40, 8, 128, 17408, 151936, 1000000, 1e-6)
    assert (model.num_layers, model.max_len) == (8, 4096)
    # and the registered default is the model as published
    from ml_trainer_tpu.models import get_model
    whole = get_model("brumby")
    assert (whole.num_layers, whole.max_len) == (40, 32768)
    cell = harness.Cell(MANIFEST, CELL)
    sizes, work = cell.sizes(), cell.work
    assert cell.traffic == harness.load_json(
        os.path.join(ROOT, "benchmark", "traffic", "reason-4k.json"))
    assert sizes["positions"] == 4096 and sizes["published_layers"] == 40
    # the arithmetic of the file's `memory`, reckoned from the work module
    assert work.state_rows(sizes) == 8256
    assert work.layer_params(sizes) == 330_342_400
    assert "a layer 330.34M" in cfg["memory"]
    embedding = sizes["vocab"] * sizes["width"] * 2
    assert "8.397 GB" in cfg["memory"]
    assert (work.step_weight_bytes(sizes) + embedding) / 1e9 == pytest.approx(
        8.3975, abs=0.0005)
    assert "34.08 MB a slot a layer by count" in cfg["memory"]
    assert work.state_bytes(sizes) / 8 / 1e6 == pytest.approx(34.08, abs=0.01)
    assert "34.35 MB as laid out" in cfg["memory"]
    pool = 16 * work.state_bytes(sizes, as_laid_out=True)
    assert pool == 16 * 8 * 8 * 8320 * 129 * 4
    assert "16 slots 4.396 GB" in cfg["memory"]
    assert pool / 1e9 == pytest.approx(4.3962, abs=0.0005)
    assert "together 12.79 GB" in cfg["memory"]
    assert (work.step_weight_bytes(sizes) + embedding + pool) / 1e9 == (
        pytest.approx(12.79, abs=0.005))
    # a step's least bytes: the state's two passes are over half of them
    step = work.step_weight_bytes(sizes) + 2 * 16 * work.state_bytes(sizes)
    assert 2 * 16 * work.state_bytes(sizes) / step == pytest.approx(
        0.56, abs=0.005)
    # the quadratic form is the cheaper at every prompt the context admits
    assert all(work.retention_prompt_flops(sizes, n)
               < n * work.retention_step_flops(sizes)
               for n in (1, 64, 2048, 4096, 9162))
    assert work.retention_prompt_flops(sizes, 9163) == (
        9163 * work.retention_step_flops(sizes))


def test_the_cell_is_on_the_five_accepted_lists_and_its_own_five():
    """What this cell needs of the manifest, and nothing of what later PRs
    may add to it: further cells, other chips, this cell on further lists."""
    b = harness.load_json(MANIFEST)
    (own,) = [w for w in b["workloads"] if w["name"] == CELL]
    assert (own["config"], own["traffic"], own["chips"]) == (
        "brumby-14b-l8", "reason-4k", 1)
    listed = {m["name"] for section in ("end_to_end", "per_layer")
              for m in b[section] if CELL in m.get("workloads", [])}
    assert listed >= {
        "serve_tokens_per_s", "prefill_share_pct", "slot_occupancy_pct",
        "serve_mfu_pct", "device_idle_pct.serve", *NEW_METRICS}
    new = {m["name"]: m for m in b["per_layer"] if m["name"] in NEW_METRICS}
    assert len(new) == 5
    assert all(m["moves"] == "serve_tokens_per_s" for m in new.values())
    for name, m in new.items():
        spec = harness.load_json(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".json"))
        assert spec["layer"] == m["layer"] and spec["source"] == m["source"]
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}")
        assert callable(reader.read)
    assert new["retention_decode_step_roofline"]["unit"] == "%"
    assert new["retention_state_step_roofline"]["unit"] == "%"
    with open(os.path.join(HERE, "tiny", "cells", CELL + ".json")) as fp:
        rehearsal = json.load(fp)
    assert not any("roofline" in name
                   for name in rehearsal["cpu_layer_metrics"])
    assert {"retention_gate_mean", "retention_norm_min"} <= set(
        rehearsal["cpu_layer_metrics"])
