"""The seam between the harness and an architecture, held by a second one
that new files alone bring (``tiny/references/other.py``, ``tiny/work/
other.py``, two configurations written with another family's keys, two files
under ``tiny/cells/``): it rehearses through the drivers' own path while
every function of GPT-2's two modules raises, so a call that the seam missed
fails here; and a configuration that does not name its architecture is
refused by name.
"""

import json
import os
import shutil

import pytest

from benchmark import harness
from benchmark.readers import serve_mfu, trace_roofline, train_mfu
from benchmark.references import gpt2 as gpt2_reference
from benchmark.work import gpt2 as gpt2_work

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture
def gpt2_raises(monkeypatch):
    """Every function the two GPT-2 modules define raises when called."""
    def refuse(module, name):
        def raiser(*a, **kw):
            raise AssertionError(
                f"{module.__name__}.{name} was called for a configuration "
                "that names another architecture")
        return raiser

    patched = []
    for module in (gpt2_reference, gpt2_work):
        for name, value in list(vars(module).items()):
            if callable(value) and getattr(
                    value, "__module__", None) == module.__name__:
                monkeypatch.setattr(module, name, refuse(module, name))
                patched.append(f"{module.__name__}.{name}")
    assert {"benchmark.references.gpt2.sizes_of",
            "benchmark.references.gpt2.make_weights",
            "benchmark.references.gpt2._block_jit",
            "benchmark.references.gpt2.served_token_gaps",
            "benchmark.references.gpt2.train_steps",
            "benchmark.work.gpt2.decode_flops",
            "benchmark.work.gpt2.flash_train"} <= set(patched)
    with pytest.raises(AssertionError):
        gpt2_reference.sizes_of({})


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("name", ["tiny.other-closed", "tiny.other-train"])
def test_a_second_architecture_rehearses_with_gpt2s_modules_out_of_reach(
        gpt2_raises, tiny, rehearse, name, trace):
    cell = harness.Cell(tiny, name)
    assert cell.config["reference"] == cell.config["work"] == "other"
    assert "n_embd" not in cell.config and "hidden_size" in cell.config
    assert cell.reference.__file__.startswith(os.path.join(HERE, "tiny"))
    rehearse(name, trace, seed=2**31 + 19)


def test_the_readers_take_the_work_counts_from_the_run(gpt2_raises, tiny):
    """On a CPU the rehearsal filters every share of a peak out, so the
    three readers that count work are driven here on made-up readings: they
    take the counts from ``ctx["work"]`` and nowhere else."""
    cell = harness.Cell(tiny, "tiny.other-train")
    us = 1000
    trace = {"window": [0, 1000 * us], "host": [],
             "devices": {"/device:TPU:0": [["flash_fwd", 0, 500 * us]]}}
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    ctx = {"work": cell.work, "sizes": cell.sizes(), "peaks": peaks,
           "chips": 1, "trace": trace, "bytes_per_value": 2,
           "window": (10.0, 20.0),
           "train": {"batch": 8, "seq_len": 128, "traced_steps": 2,
                     "tokens_per_s": 1e5},
           "records": [{"prompt_len": 16, "times": [11.0, 12.0, 13.0]}]}
    ops, moved = cell.work.flash_train(ctx)
    assert trace_roofline.read(ctx, "flash", "flash_train") == pytest.approx(
        100.0 * max(ops / 1e12, moved / 1e11) / 500e-6)
    assert train_mfu.read(ctx) == pytest.approx(
        100.0 * cell.work.train_flops_per_token(ctx["sizes"], 128) * 1e5
        / 1e12)
    served = (cell.work.prefill_flops(ctx["sizes"], 16)
              + cell.work.decode_flops(ctx["sizes"], 16)
              + cell.work.decode_flops(ctx["sizes"], 17))
    assert serve_mfu.read(ctx) == pytest.approx(100.0 * served / (10 * 1e12))


@pytest.mark.parametrize("key,value,words", [
    ("reference", None, ["'reference'", "names no"]),
    ("work", None, ["'work'", "names no"]),
    ("reference", "not-there", ["references/not-there.py"]),
    ("work", "not-there", ["work/not-there.py"]),
])
def test_a_configuration_that_does_not_name_its_architecture_is_refused(
        tmp_path, tiny, key, value, words):
    """No default: a file without the key, or naming a module that is not
    there, is a ``BenchError`` that says which."""
    with open(tiny) as fp:
        manifest = json.load(fp)
    bench = tmp_path / "bench"
    shutil.copytree(os.path.join(HERE, "tiny", "configs"), bench / "configs")
    shutil.copytree(os.path.join(HERE, "tiny", "traffic"), bench / "traffic")
    for c in manifest["configs"]:
        c["file"] = str(bench / "configs" / os.path.basename(c["file"]))
    path = bench / "configs" / "gpt2-tiny-serve.json"
    config = json.loads(path.read_text())
    assert config.pop(key) == "gpt2"
    if value is not None:
        config[key] = value
    path.write_text(json.dumps(config))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    with pytest.raises(harness.BenchError) as e:
        harness.Cell(str(tmp_path / "BENCHMARK.json"), "tiny.closed")
    assert all(w in str(e.value) for w in words), str(e.value)
    assert e.value.code == 2
    # the untouched file beside it still loads
    harness.Cell(str(tmp_path / "BENCHMARK.json"), "tiny.train").sizes()
