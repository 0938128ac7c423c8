"""The benchmark's side of ``exaone_moe`` (K-EXAONE), on the CPU at the tiny
size of ``tiny/configs/exaone-moe-tiny-serve.json``: the plain reference
against the program through prefill and both caches, the runs that must come
out as NOT correct (the fp8 control, a token altered where it is produced),
the new mix's means, the work module's counts against a hand count, the two
new readers, and what the configuration's file has to say.  The rehearsal of
the cell itself is ``tiny/cells/k-exaone-236b-ep8.sharegpt-2k.json``, run by
``test_benchmark_cells.py`` with every other.
"""

import json
import os

import numpy as np
import pytest

from benchmark import harness, loadgen
from benchmark.readers import span_arg_stat, step_roofline

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
CELL, TINY = "k-exaone-236b-ep8.sharegpt-2k", "tiny.exaone-closed"


def test_reference_agrees_with_the_program_through_prefill_and_both_caches(
        tiny):
    """The weights the reference makes ARE the program's tree (names, shapes
    and precisions); the program in float32 reads the reference's logits
    through a prefill longer than the window and decode steps that wrap the
    rings (float32 sums in another order: 1e-5 of the largest logit; the
    program in the stated bfloat16 reads a thousand times that)."""
    import jax
    import jax.numpy as jnp

    cell = harness.Cell(tiny, TINY)
    reference, sizes = cell.reference, cell.sizes()
    assert sizes["experts"] == 16 and sizes["experts_held"] == (0, 4)
    weights = harness.make_weights(cell, 2**31 + 5)
    stated = harness.build_model(cell.config)
    shapes = jax.eval_shape(
        lambda: stated.init({"params": jax.random.PRNGKey(0)},
                            jnp.zeros((1, 8), jnp.int32), train=False))
    assert (jax.tree.map(lambda s: (s.shape, s.dtype), shapes["params"])
            == jax.tree.map(lambda w: (w.shape, w.dtype), weights))
    kinds = {str(w.dtype) for w in jax.tree.leaves(weights)}
    assert kinds == {"bfloat16", "float32"}
    assert weights["block1"]["moe"]["wg"].dtype == jnp.bfloat16
    assert weights["block1"]["moe"]["router"].dtype == jnp.float32

    ids = np.random.default_rng(0).integers(0, sizes["vocab"], size=(2, 32))
    want = np.asarray(reference.logits(weights, ids, sizes))

    def through_the_caches(model):
        dm = model.clone(decode=True)
        cache = jax.tree.map(jnp.zeros_like, dm.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((2, 1), jnp.int32),
            train=False)["cache"])
        step = jax.jit(lambda cache, tok: dm.apply(
            {"params": weights, "cache": cache}, tok, train=False,
            mutable=["cache"]))
        out, mut = step(cache, jnp.asarray(ids[:, :12]))
        rows = [np.asarray(out)]
        for t in range(12, 32):
            out, mut = step(mut["cache"], jnp.asarray(ids[:, t:t + 1]))
            rows.append(np.asarray(out))
        return np.concatenate(rows, axis=1)

    exact = through_the_caches(stated.clone(dtype=jnp.float32))
    assert np.abs(exact - want).max() < 1e-5 * np.abs(want).max()
    rounded = through_the_caches(stated)
    assert np.abs(rounded - want).max() > 1e-3 * np.abs(want).max()
    # The serving comparison reads gaps of zero for the reference's own
    # greedy tokens, and a plain gap for a token that is not the best.
    seq = list(ids[0, :12])
    for _ in range(8):
        row = reference.logits(weights, np.asarray([seq + [0] * (20 - len(seq))]),
                               sizes)
        seq.append(int(np.asarray(row)[0, len(seq) - 1].argmax()))
    served = np.asarray(seq[12:])
    gaps = reference.served_token_gaps(weights, sizes, ids[0, :12], served)
    assert gaps.shape == (8,) and gaps.max() < 1e-5
    served[3] = (served[3] + 1) % sizes["vocab"]
    bad = reference.served_token_gaps(weights, sizes, ids[0, :12], served)
    assert bad[3] > 0.01 and bad[:3].max() < 1e-5


def test_the_fp8_control_in_the_programs_place_is_not_correct(tiny):
    """Free of any clock: the program's own ``generate()`` answers a block of
    the schedule in the stated bfloat16; its tokens are checked and judged as
    a run's are and come out correct, and the fp8 control put in the
    program's place (``lower``: the experts' and the router's products
    rounded too) does not.  The limit is this size's own, between the two
    readings over 768 tokens a seed: the program 6.6e-6 to 1.8e-5, the
    control 8.8e-4 to 1.7e-3 (CPU, five seeds)."""
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
                   "limits": {"served_token_gap_mean": 1e-4}}
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


# ------------------------------------------------------------- the new mix
def test_sharegpt_2k_has_the_means_and_the_filter_of_its_source():
    """ShareGPT as vLLM's benchmark replays it (Kwon et al. 2023, figure
    11): mean input 161.31 tokens, mean output 337.99, after every clip,
    under the source's OWN filter (prompt to 1,024, sum to 2,048)."""
    traffic = harness.load_json(
        os.path.join(ROOT, "benchmark", "traffic", "sharegpt-2k.json"))
    assert "arXiv:2309.06180" in traffic["source"]
    block = loadgen.base_block(traffic)
    assert block["prompt_len"].mean() == pytest.approx(161.31, rel=0.01)
    assert block["output_len"].mean() == pytest.approx(337.99, rel=0.01)
    assert (traffic["prompt_len"]["max"], traffic["max_total"]) == (1024, 2048)
    assert (block["prompt_len"] + block["output_len"]).max() <= 2048
    assert block["prompt_len"].min() >= 16 and block["output_len"].min() >= 4
    cell = harness.Cell(MANIFEST, CELL)
    assert cell.traffic == traffic and cell.sizes()["positions"] == 2048
    # the same seed the same bytes, ids from the rows of the vocabulary held
    first = [next(loadgen.iter_schedule(traffic, 19200, 2**31 + 11))
             for _ in range(2)]
    assert first[0] == first[1] and max(first[0]["prompt"]) < 19200


# ------------------------------------------------------------ work counts
def test_work_counts_against_a_hand_count_at_the_tiny_sizes(tiny):
    cell = harness.Cell(tiny, TINY)
    work, s = cell.work, cell.sizes()
    attn = 64 * (64 + 32 + 32) + 64 * 64        # q, k, v, o: 12,288
    assert work.attention_params(s) == attn
    gated = 3 * 64 * 32                          # one expert: 6,144
    assert work.gated_params(s, 32) == gated
    assert work.expected_held(s) == 2 * 4 / 16   # half an assignment a token
    dense = 2 * (attn + 3 * 64 * 96)
    sparse = 2 * (attn + 64 * 16 + (1 + 0.5) * gated)
    assert work.layer_matmul_flops_per_token(s, "dense") == dense
    assert work.layer_matmul_flops_per_token(s, "sparse") == sparse
    head = 2 * 64 * 256
    # a token at context 20: a window layer sees 8 keys, a full one 21
    keys = 6 * 8 + 2 * 21
    assert work.decode_flops(s, 20) == (
        head + dense + 7 * sparse + 4 * 64 * keys)
    # inside the window every layer sees t + 1
    assert work.decode_flops(s, 3) == head + dense + 7 * sparse + 4 * 64 * 8 * 4
    # a prompt of 11: full layers 66 pairs, window layers 36 + 3 * 8
    assert work.prefill_flops(s, 11) == (
        head + 11 * (dense + 7 * sparse) + 4 * 64 * (2 * 66 + 6 * 60))
    assert work.prefill_flops(s, 5) == (
        head + 5 * (dense + 7 * sparse) + 4 * 64 * 8 * 15)
    with pytest.raises(NotImplementedError, match="served only"):
        work.train_flops_per_token(s, 128)
    # bytes of a step: every leaf of the tree but the embedding's rows
    import jax

    weights = harness.make_weights(cell, 3)
    whole = sum(w.size * w.dtype.itemsize for w in jax.tree.leaves(weights))
    assert work.step_weight_bytes(s) == whole - 256 * 64 * 2
    assert work.cache_bytes_read(s, 20) == 2 * 32 * 2 * keys
    # one step a row: two rows, contexts 20 and 3, three steps in the window
    ctx = {"sizes": s, "window": (10.0, 20.0),
           "samples": {"step_secs": [0.5, 0.25, 0.75]},
           "records": [{"prompt_len": 20, "times": [9.0, 11.0, 25.0]},
                       {"prompt_len": 3, "times": [12.0, 13.0]}]}
    ops, moved = work.decode_step_work(ctx)
    assert ops == pytest.approx(
        (work.decode_flops(s, 20) + work.decode_flops(s, 3)) / 3)
    assert moved == pytest.approx(work.step_weight_bytes(s) + (
        work.cache_bytes_read(s, 20) + work.cache_bytes_read(s, 3)
        + 2 * 64 * 2) / 3)
    # the reader: silent without a device trace, else least over mean
    ctx.update(work=work, peaks={"bf16_flops_per_s": 1e9,
                                 "hbm_bytes_per_s": 1e6})
    assert step_roofline.read(ctx, work="decode_step_work") is None
    ctx["trace_reduced"] = {"busy_s": 1.0}
    assert step_roofline.read(ctx, work="decode_step_work") == pytest.approx(
        100.0 * max(ops / 1e9, moved / 1e6) / 0.5)
    assert ctx["notes"]["decode_step_work_bound"] == "memory"
    assert step_roofline.read({**ctx, "records": []},
                              work="decode_step_work") is None


def test_span_arguments_are_read_over_the_window_and_no_further():
    import time

    from ml_trainer_tpu.telemetry import spans

    name = "test_exaone.fence"

    def fence(**args):
        with spans.span(name) as out:
            out.update(args)

    fence(expert_rows=1000.0, routed_rows=1.0)           # before the window
    t0 = time.monotonic()
    fence(expert_rows=6.0, expert_rows_max=3.0, routed_rows=48.0)
    fence(expert_rows=10.0, expert_rows_max=5.0, routed_rows=80.0)
    with spans.span(name):                               # carries no counter
        pass
    t1 = time.monotonic()
    ctx = {"window": (t0, t1), "sizes": {"experts_held": (0, 4)}}
    assert span_arg_stat.read(ctx, [name], "expert_rows") == pytest.approx(8.0)
    assert span_arg_stat.read(ctx, [name], "expert_rows", over="routed_rows",
                              scale=100.0) == pytest.approx(12.5)
    assert span_arg_stat.read(
        ctx, [name], "expert_rows_max", over="expert_rows",
        times_size="experts_held") == pytest.approx(2.0)
    assert span_arg_stat.read(ctx, [name], "no_such_counter") is None
    assert span_arg_stat.read(ctx, ["no_such_span"], "expert_rows") is None


# ------------------------------------------------------ the configuration
def test_the_configuration_states_its_cut_and_the_program_runs_its_widths():
    b = harness.load_json(MANIFEST)
    entry = {c["name"]: c for c in b["configs"]}["k-exaone-236b-ep8"]
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_nextn_predict_layers", "max_position_embeddings"]
    assert cfg["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600,
        "num_nextn_predict_layers": 1, "max_position_embeddings": 262144}
    assert [cfg[k] for k in cfg["reduced"]] == [8, 16, 19200, 0, 2048]
    for key in ("deployment", "assumed", "precision", "limits", "memory"):
        assert cfg[key], key
    assert cfg["precision"]["stated"] == "bfloat16"
    assert cfg["program"]["server_options"] == {
        "max_batch": 64, "max_queue": 128, "watchdog_timeout": 900.0}
    # nested groups are copied whole and read up to the depth kept
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 48
    # the program's model at this configuration runs every published width
    model = harness.build_model(cfg)
    assert (model.embed_dim, model.num_heads, model.num_kv_heads,
            model.head_dim, model.dense_dim, model.expert_dim,
            model.num_experts, model.num_experts_per_tok, model.window,
            model.routed_scaling, model.num_shared_experts, model.eps,
            model.rope_theta) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["num_key_value_heads"], cfg["head_dim"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        cfg["published"]["num_experts"], cfg["num_experts_per_tok"],
        cfg["sliding_window"], cfg["routed_scaling_factor"],
        cfg["num_shared_experts"], cfg["rms_norm_eps"],
        cfg["rope_parameters"]["rope_theta"])
    assert model.layer_types == tuple(cfg["layer_types"][:8])
    assert model.mlp_layer_types == tuple(cfg["mlp_layer_types"][:8])
    assert (model.vocab_size, model.max_len, tuple(model.experts_held)) == (
        19200, 2048, (0, 16))
    sizes = harness.Cell(MANIFEST, CELL).sizes()
    work = harness.Cell(MANIFEST, CELL).work
    # the arithmetic of the file's `memory`: 11.96 GB of weights
    embedding = sizes["vocab"] * sizes["width"] * 2
    assert (work.step_weight_bytes(sizes) + embedding) / 1e9 == pytest.approx(
        11.97, abs=0.01)
    # the cell's new metrics are listed for it and for no other cell
    new = {m["name"]: m for m in b["per_layer"] if m["name"] in (
        "expert_rows_per_step", "expert_held_share_pct",
        "expert_load_max_over_mean", "decode_step_roofline")}
    assert len(new) == 4
    assert all(m["workloads"] == [CELL] and m["source"] == "program_counter"
               for m in new.values())
    with open(os.path.join(HERE, "tiny", "cells", CELL + ".json")) as fp:
        rehearsal = json.load(fp)
    assert "decode_step_roofline" not in rehearsal["cpu_layer_metrics"]
    assert set(new) - {"decode_step_roofline"} <= set(
        rehearsal["cpu_layer_metrics"])
