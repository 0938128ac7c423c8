"""chip_smoke.py's own phase code on the CPU mesh: ``gpt2_tiny`` width,
kernels in interpret mode.  A CPU can state what the smoke REFUSES — a
request ending in ``error``, ``engine_errors > 0``, a non-``tpu`` platform
— and the two rules the chip run depends on: ``backend='tpu'`` never
falls back to the host unasked, and a compile cache placed from outside
stays where it was put."""

import dataclasses
import os
import types

import jax
import pytest

import chip_smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dataclasses.replace(
    chip_smoke.FULL, model="gpt2_tiny", vocab_size=256, seq_len=64,
    platform="cpu", interpret=True, prompt_lens=(5, 19), new_tokens=4,
)


def test_phases_pass_at_tiny_width(tmp_path, capsys):
    chip_smoke.check_kernels(TINY)
    assert chip_smoke.child_main("serve", str(tmp_path), TINY) == 0
    out = capsys.readouterr().out
    assert chip_smoke.RESULT_TAG in out
    for mode in TINY.serve_modes:
        assert f"serve [{mode}]" in out
    assert "compiles after warm-up: 0" in out


def test_mosaic_call_sites_follows_calls_not_text():
    """A jitted kernel wrapper is one function in the lowered text, called
    by every layer: the count the kernels phase holds to the model's depth
    is of executed calls."""
    text = """
module @jit_step {
  func.func public @main(%arg0: tensor<4xf32>) -> tensor<4xf32> {
    %0 = call @layer(%arg0) : (tensor<4xf32>) -> tensor<4xf32>
    %1 = call @layer(%0) : (tensor<4xf32>) -> tensor<4xf32>
    %2 = stablehlo.custom_call @tpu_custom_call(%1) : (tensor<4xf32>)
    return %2 : tensor<4xf32>
  }
  func.func private @layer(%arg0: tensor<4xf32>) -> tensor<4xf32> {
    %0 = call @_flash_forward(%arg0) : (tensor<4xf32>) -> tensor<4xf32>
    return %0 : tensor<4xf32>
  }
  func.func private @_flash_forward(%arg0: tensor<4xf32>) -> tensor<4xf32> {
    %0 = stablehlo.custom_call @tpu_custom_call(%arg0) : (tensor<4xf32>)
    return %0 : tensor<4xf32>
  }
}"""
    assert text.count("tpu_custom_call") == 2
    assert chip_smoke.mosaic_call_sites(text) == 3


def test_non_tpu_platform_is_a_nonzero_exit(tmp_path, capsys):
    on_chip = dataclasses.replace(TINY, platform="tpu")
    for phase in chip_smoke.PHASES:
        assert chip_smoke.child_main(
            phase, str(tmp_path), on_chip
        ) == chip_smoke.EXIT_WRONG_PLATFORM
    out = capsys.readouterr().out
    assert "platform is 'cpu'" in out and chip_smoke.RESULT_TAG not in out


def test_engine_error_fails_the_serve_phase(tmp_path, capsys, monkeypatch):
    """The serve loop survives an engine exception by design; the smoke
    must not: the dead requests and the counter each fail the phase."""
    from ml_trainer_tpu.serving.engine import SlotDecodeEngine

    real_step, calls = SlotDecodeEngine.advance, []

    def step_once_refused(self):
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("Mosaic refused the decode step")
        return real_step(self)

    # ``advance`` is the serving loop's call of the decode step.
    monkeypatch.setattr(SlotDecodeEngine, "advance", step_once_refused)
    one_mode = dataclasses.replace(TINY, serve_modes=("contiguous",))
    assert chip_smoke.child_main("serve", str(tmp_path), one_mode) == 1
    out = capsys.readouterr().out
    assert "FAIL [serve]" in out and "'error'" in out
    assert chip_smoke.RESULT_TAG not in out

    # Each condition alone, on a recorded outcome.
    def outcome(states, **metrics):
        served = {"engine_errors": 0, "watchdog_trips": 0,
                  "requests_completed": len(states), "tokens_total": 0,
                  "max_active_slots": 1, **metrics}
        monkeypatch.setattr(
            chip_smoke, "_get_json",
            lambda url, path: served if path == "/metrics.json"
            else {"requests_failed": 0},
        )
        server = types.SimpleNamespace(slo=types.SimpleNamespace(
            timelines=lambda: [{"state": s} for s in states]))
        return chip_smoke.check_serve_outcome(
            server, "http://unused", len(states))

    outcome(["done", "done"])
    with pytest.raises(chip_smoke.SmokeFailure, match="state 'done'"):
        outcome(["done", "error"])
    with pytest.raises(chip_smoke.SmokeFailure, match="engine_errors=1"):
        outcome(["done", "done"], engine_errors=1)


def test_backend_tpu_without_a_cpu_pin_raises(monkeypatch):
    from ml_trainer_tpu import MLModel, Trainer
    from ml_trainer_tpu.trainer import cpu_pinned

    assert cpu_pinned()  # conftest pins the test mesh explicitly
    Trainer(MLModel())  # default backend='tpu' on the pinned CPU mesh: fine
    # The same machine with nothing pinned: JAX would have come up on the
    # CPU all the same, and the default backend must not accept that.
    # ("tpu,cpu" is what the TPU machine sets: the CPU second is no pin.)
    try:
        for platforms in (None, "tpu,cpu"):
            jax.config.update("jax_platforms", platforms)
            assert not cpu_pinned()
            with pytest.raises(RuntimeError, match="no TPU is attached"):
                Trainer(MLModel())
    finally:
        jax.config.update("jax_platforms", "cpu")


def test_cache_dir_placed_from_outside_is_left_alone(monkeypatch, tmp_path):
    from ml_trainer_tpu import trainer

    assert trainer.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    # Not the CPU skip's doing: take the pin away for the call.
    monkeypatch.setattr(trainer, "cpu_pinned", lambda: False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    trainer.enable_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        trainer.enable_compilation_cache()
        assert jax.config.jax_compilation_cache_dir == (
            trainer.COMPILE_CACHE_DIR
        )
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    # One place sets a directory: the package names the option once.
    hits = []
    for root, _dirs, files in os.walk(os.path.join(REPO, "ml_trainer_tpu")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(root, name), encoding="utf-8") as f:
                    if '"jax_compilation_cache_dir"' in f.read():
                        hits.append(name)
    assert hits == ["trainer.py"]
