"""The benchmark's side of ``kimi_linear`` (Kimi Linear), on the CPU at the
tiny size of ``tiny/configs/kimi-linear-tiny-serve.json``: the plain
reference against the program through prefill, recurrent state, convolution
tails and latent cache, the runs that must come out as NOT correct (the fp8
control, a token altered where it is produced), the new mix's block as its
file states it, the work module's counts against a hand count, and what the
configuration's file has to say.  The rehearsal of the cell itself is
``tiny/cells/kimi-linear-48b-ep8.reason-4k.json``, run by
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
CELL, TINY = "kimi-linear-48b-ep8.reason-4k", "tiny.kimi-closed"


def test_reference_agrees_with_the_program_through_state_and_latent_cache(
        tiny):
    """The weights the reference makes ARE the program's tree (names, shapes
    and precisions); the program in float32 reads the reference's logits
    through a prefill longer than a chunk (the chunked form against the
    reference's token-by-token scan) and decode steps through state, tails
    and latent cache (the absorbed attention against the expanded): float32
    sums in another order, 2e-5 of the largest logit; the program in the
    stated bfloat16 reads a thousand times that."""
    import jax
    import jax.numpy as jnp

    cell = harness.Cell(tiny, TINY)
    reference, sizes = cell.reference, cell.sizes()
    assert sizes["experts"] == 16 and sizes["experts_held"] == (0, 4)
    assert [kind for kind, _ in sizes["layer_kinds"]] == (
        ["kda"] * 3 + ["mla"]) * 2
    assert [sparse for _, sparse in sizes["layer_kinds"]] == (
        [False] + [True] * 7)
    weights = harness.make_weights(cell, 2**31 + 5)
    stated = harness.build_model(cell.config)
    shapes = jax.eval_shape(
        lambda: stated.init({"params": jax.random.PRNGKey(0)},
                            jnp.zeros((1, 8), jnp.int32), train=False))
    assert (jax.tree.map(lambda s: (s.shape, s.dtype), shapes["params"])
            == jax.tree.map(lambda w: (w.shape, w.dtype), weights))
    kinds = {str(w.dtype) for w in jax.tree.leaves(weights)}
    assert kinds == {"bfloat16", "float32"}
    kda, mla = weights["block0"]["attn"], weights["block3"]["attn"]
    assert kda["conv"].dtype == mla["kv_up"].dtype == jnp.bfloat16
    assert kda["A_log"].dtype == kda["dt_bias"].dtype == jnp.float32
    assert weights["block1"]["moe"]["router"].dtype == jnp.float32
    # the decays a seed draws run from nearly none to most of the state
    alpha = np.exp(-np.exp(np.asarray(kda["A_log"]))[:, None] * np.log1p(
        np.exp(np.asarray(kda["dt_bias"]).reshape(4, 16))))
    assert 0.15 < alpha.min() < 0.95 < alpha.max() < 1.0

    ids = np.random.default_rng(0).integers(0, sizes["vocab"], size=(2, 32))
    want = np.asarray(reference.logits(weights, ids, sizes))

    def through_the_caches(model):
        dm = model.clone(decode=True)
        cache = jax.tree.map(jnp.zeros_like, dm.init(
            {"params": jax.random.PRNGKey(0)}, jnp.zeros((2, 1), jnp.int32),
            train=False)["cache"])
        assert cache["block0"]["attn"]["state"].shape == (2, 4, 16, 16)
        assert cache["block0"]["attn"]["state"].dtype == jnp.float32
        assert cache["block0"]["attn"]["conv_tail"].shape == (2, 3, 192)
        assert cache["block3"]["attn"]["latent"].shape == (2, 1, 64, 40)
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
    assert np.abs(exact - want).max() < 2e-5 * np.abs(want).max()
    rounded = through_the_caches(stated)
    assert np.abs(rounded - want).max() > 2e-3 * np.abs(want).max()
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
    program's place (``lower``: the experts', the router's and both
    low-rank gates' products rounded too) does not.  The limit is this
    size's own, between the two readings over 768 tokens a seed (CPU, three
    seeds; ``limits_note`` of the tiny configuration gives them)."""
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
                   "limits": {"served_token_gap_mean": 6e-5}}
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
def test_reason_4k_is_the_block_its_file_states():
    """``reason-4k.json``'s ``stated`` line, number by number: the clipped
    means, the share of pairs that ``max_total`` cuts, the mean live context
    (a request holds ``prompt + k`` positions while its k-th token is the
    newest) and the true share of the padded prompt tokens."""
    traffic = harness.load_json(
        os.path.join(ROOT, "benchmark", "traffic", "reason-4k.json"))
    assert "workloads.md" in traffic["source"]
    block = loadgen.base_block(traffic)
    prompts, outputs = block["prompt_len"], block["output_len"]
    assert "prompts mean 667 (64 to 2,048)" in traffic["stated"]
    assert round(prompts.mean()) == 667
    assert (prompts.min(), prompts.max()) == (64, 2048)
    assert "outputs mean 1,055 (182 to 3,072)" in traffic["stated"]
    assert round(outputs.mean()) == 1055
    assert (outputs.min(), outputs.max()) == (182, 3072)
    uncut = loadgen.base_block({**traffic, "max_total": 10**6})["output_len"]
    assert "2 of the 128 pairs cut by max_total" in traffic["stated"]
    assert int((uncut != outputs).sum()) == 2
    assert (prompts + outputs).max() == traffic["max_total"] == 4096
    live = (prompts * outputs + outputs * (outputs + 1) / 2).sum() / outputs.sum()
    assert "mean live context 1,392" in traffic["stated"]
    assert live == pytest.approx(1392, abs=1)
    buckets = 2 ** np.ceil(np.log2(prompts))
    assert "true over padded prompt tokens 73%" in traffic["stated"]
    assert prompts.sum() / buckets.sum() == pytest.approx(0.73, abs=0.005)
    assert (traffic["loop"], traffic["clients_per_slot"], traffic["block"],
            traffic["lead_in_s"], traffic["trace_s"]) == (
        "closed", 2, 128, 12.0, 1.0)
    cell = harness.Cell(MANIFEST, CELL)
    assert cell.traffic == traffic and cell.sizes()["positions"] == 4096
    # the same seed the same bytes, ids from the rows of the vocabulary held
    first = [next(loadgen.iter_schedule(traffic, 20480, 2**31 + 11))
             for _ in range(2)]
    assert first[0] == first[1] and max(first[0]["prompt"]) < 20480


# ------------------------------------------------------------ work counts
def test_work_counts_against_a_hand_count_at_the_tiny_sizes(tiny):
    cell = harness.Cell(tiny, TINY)
    work, s = cell.work, cell.sizes()
    kda = 4 * 64 * 64 + 2 * (64 * 16 + 16 * 64) + 64 * 4      # 20,736
    assert work.kda_params(s) == kda
    core = 6 * 4 * 16 * 16 + 2 * 4 * 192                      # 7,680
    assert work.kda_core_flops(s) == core
    mla = 64 * 4 * 24 + 64 * 40 + 32 * 4 * 32 + 4 * 16 * 64   # 16,896
    assert work.mla_params(s) == mla
    gated = 3 * 64 * 32                          # one expert: 6,144
    assert work.gated_params(s, 32) == gated
    assert work.expected_held(s) == 2 * 4 / 16   # half an assignment a token
    dense, sparse = 2 * 3 * 64 * 96, 2 * (64 * 16 + (1 + 0.5) * gated)
    assert work.ffn_flops_per_token(s, False) == dense
    assert work.ffn_flops_per_token(s, True) == sparse
    # KKKM KKKM, the first layer dense
    token = 6 * (2 * kda + core) + 2 * 2 * mla + dense + 7 * sparse
    head = 2 * 64 * 256
    # a token at context 20 attends 21 keys, absorbed: 2 x 32 + 8 a key
    assert work.decode_flops(s, 20) == head + token + 2 * 2 * 4 * 72 * 21
    # a prompt of 11: 66 pairs, expanded: 16 + 8 + 16 a pair
    assert work.prefill_flops(s, 11) == (
        head + 11 * token + 2 * 2 * 4 * 40 * 66)
    with pytest.raises(NotImplementedError, match="served only"):
        work.train_flops_per_token(s, 128)
    # bytes of a step: every leaf of the tree but the embedding's rows
    import jax

    weights = harness.make_weights(cell, 3)
    whole = sum(w.size * w.dtype.itemsize for w in jax.tree.leaves(weights))
    assert work.step_weight_bytes(s) == whole - 256 * 64 * 2
    # a slot's state: 6 layers of 4 x 16 x 16 float32 and 3 x 192 bfloat16
    assert work.state_bytes(s) == 6 * (4 * 16 * 16 * 4 + 3 * 192 * 2)
    assert work.latent_bytes_read(s, 20) == 2 * 21 * 40 * 2
    # one step a row: two rows, contexts 20 and 3, three steps in the window
    ctx = {"sizes": s, "slots": 4, "window": (10.0, 20.0),
           "samples": {"step_secs": [0.5, 0.25, 0.75]},
           "records": [{"prompt_len": 20, "times": [9.0, 11.0, 25.0]},
                       {"prompt_len": 3, "times": [12.0, 13.0]}]}
    ops, moved = work.decode_step_work(ctx)
    assert ops == pytest.approx(
        (work.decode_flops(s, 20) + work.decode_flops(s, 3)) / 3)
    # every slot's state read and written, whatever the rows in flight
    assert moved == pytest.approx(
        work.step_weight_bytes(s) + 2 * 4 * work.state_bytes(s) + (
            work.latent_bytes_read(s, 20) + work.latent_bytes_read(s, 3)
            + 2 * 64 * 2) / 3)
    # the reader: silent without a device trace, else least over mean
    ctx.update(work=work, peaks={"bf16_flops_per_s": 1e9,
                                 "hbm_bytes_per_s": 1e6})
    assert step_roofline.read(ctx, work="decode_step_work") is None
    ctx["trace_reduced"] = {"busy_s": 1.0}
    assert step_roofline.read(ctx, work="decode_step_work") == pytest.approx(
        100.0 * max(ops / 1e9, moved / 1e6) / 0.5)
    assert ctx["notes"]["decode_step_work_bound"] == "memory"


def test_the_true_share_of_a_prefill_is_read_from_its_span():
    import time

    from ml_trainer_tpu.telemetry import spans

    spec = harness.load_json(os.path.join(
        ROOT, "benchmark", "layer_metrics", "prefill_true_share_pct.json"))
    assert spec["reader"] == "span_arg_stat"
    assert spec["params"]["names"] == ["serve_prefill"]
    name = "test_kimi.prefill"
    params = {**spec["params"], "names": [name]}
    with spans.span(name, prompt_tokens=1000, bucket_tokens=1024):
        pass                                             # before the window
    t0 = time.monotonic()
    with spans.span(name, prompt_tokens=11, bucket_tokens=16):
        pass
    with spans.span(name, prompt_tokens=37, bucket_tokens=64):
        pass
    with spans.span(name, prompt_len=5, bucket=8):       # an older program's
        pass
    ctx = {"window": (t0, time.monotonic()), "sizes": {}}
    assert span_arg_stat.read(ctx, **params) == pytest.approx(100 * 48 / 80)
    assert span_arg_stat.read(ctx, **{**params, "names": ["no_such"]}) is None


# ------------------------------------------------------ the configuration
def test_the_configuration_states_its_cut_and_the_program_runs_its_widths():
    b = harness.load_json(MANIFEST)
    entry = {c["name"]: c for c in b["configs"]}["kimi-linear-48b-ep8"]
    cfg = harness.load_json(os.path.join(ROOT, entry["file"]))
    assert cfg["catalog_name"] == "Kimi-Linear-48B-A3B-Instruct"
    assert cfg["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size", "model_max_length"]
    assert cfg["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840,
        "model_max_length": 1048576}
    assert [cfg[k] for k in cfg["reduced"]] == [12, 32, 20480, 4096]
    for key in ("deployment", "assumed", "precision", "limits", "memory",
                "expert_load"):
        assert cfg[key], key
    assert cfg["precision"]["stated"] == "bfloat16"
    assert cfg["program"]["server_options"] == {
        "max_batch": 128, "max_queue": 256, "watchdog_timeout": 900.0}
    # nested groups are copied whole and read up to the depth kept
    linear = cfg["linear_attn_config"]
    assert len(linear["kda_layers"]) == 20
    assert linear["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    # the program's model at this configuration runs every published width
    model = harness.build_model(cfg)
    assert (model.embed_dim, model.num_heads, model.head_dim,
            model.conv_size, model.kv_lora_rank, model.qk_nope_head_dim,
            model.qk_rope_head_dim, model.v_head_dim, model.dense_dim,
            model.expert_dim, model.num_experts, model.num_experts_per_tok,
            model.routed_scaling, model.num_shared_experts, model.eps,
            model.first_k_dense_replace) == (
        cfg["hidden_size"], linear["num_heads"], linear["head_dim"],
        linear["short_conv_kernel_size"], cfg["kv_lora_rank"],
        cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
        cfg["intermediate_size"], cfg["moe_intermediate_size"],
        cfg["published"]["num_experts"], cfg["num_experts_per_token"],
        cfg["routed_scaling_factor"], cfg["num_shared_experts"],
        cfg["rms_norm_eps"], cfg["first_k_dense_replace"]) == (
        2304, 32, 128, 4, 512, 128, 64, 128, 9216, 1024, 256, 8, 2.446, 1,
        1e-5, 1)
    assert model.kda_layers == tuple(linear["kda_layers"])
    assert model.full_attn_layers == tuple(linear["full_attn_layers"])
    assert cfg["num_attention_heads"] == model.num_heads
    assert (model.vocab_size, model.max_len, model.num_layers,
            tuple(model.experts_held)) == (20480, 4096, 12, (0, 32))
    cell = harness.Cell(MANIFEST, CELL)
    sizes, work = cell.sizes(), cell.work
    assert sizes["layer_kinds"] == (("kda", False),) + (
        ("kda", True), ("kda", True), ("mla", True), ("kda", True)) * 2 + (
        ("kda", True), ("kda", True), ("mla", True))
    # the arithmetic of the file's `memory`, reckoned from the work module
    embedding = sizes["vocab"] * sizes["width"] * 2
    assert "= 6.35 GB, 6.37 GB as laid out" in cfg["memory"]
    assert (work.step_weight_bytes(sizes) + embedding) / 1e9 == pytest.approx(
        6.367, abs=0.002)
    assert "18.87 MB a slot, 2.42 GB" in cfg["memory"]
    state = 128 * 9 * 32 * 128 * 128 * 4
    assert state / 1e9 == pytest.approx(2.416, abs=0.001)
    assert 128 * work.state_bytes(sizes) == state + 128 * 9 * 3 * 12288 * 2
    assert "= 1.81 GB" in cfg["memory"]
    latent = 128 * work.latent_bytes_read(sizes, 4095)
    assert latent / 1e9 == pytest.approx(1.812, abs=0.001)
    assert "together 10.68 GB" in cfg["memory"]
    assert (work.step_weight_bytes(sizes) + embedding
            + 128 * work.state_bytes(sizes) + latent) / 1e9 == pytest.approx(
        10.68, abs=0.005)
    assert work.expected_held(sizes) == 1.0
    # the cell's new metrics are listed for it and for no other cell
    new = {m["name"]: m for m in b["per_layer"] if m["name"] in (
        "state_decode_step_roofline", "prefill_true_share_pct")}
    assert len(new) == 2
    assert all(m["workloads"] == [CELL] and m["source"] == "program_counter"
               and m["moves"] == "serve_tokens_per_s" for m in new.values())
    with open(os.path.join(HERE, "tiny", "cells", CELL + ".json")) as fp:
        rehearsal = json.load(fp)
    assert "state_decode_step_roofline" not in rehearsal["cpu_layer_metrics"]
    assert "prefill_true_share_pct" in rehearsal["cpu_layer_metrics"]
