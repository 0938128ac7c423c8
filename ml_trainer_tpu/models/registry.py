"""Model registry: string name -> flax module factory.

The reference has exactly one hardcoded model (ref: main.py:30); the
registry generalizes that to the north-star zoo while keeping
``Trainer(model=...)`` able to accept either a module instance or a name.
"""

from __future__ import annotations

import importlib
from typing import Callable, Dict

import flax.linen as nn

MODELS: Dict[str, Callable[..., nn.Module]] = {}

# Target-model name -> suggested draft-model name for speculative
# decoding (ml_trainer_tpu/speculative.py).  A valid pair shares one
# vocabulary — acceptance compares token ids across the two models — so
# the pairing is registered next to the models instead of guessed at
# call sites.
DRAFT_PAIRS: Dict[str, str] = {
    "gpt2_mini": "gpt2_nano",
    # The 50257-vocab family has no small partner in the zoo yet
    # (gpt2_tiny's synthetic 1024 vocab is NOT compatible); the n-gram
    # drafter covers those targets model-free.
}

_FAMILY_MODULES = (
    "mlmodel", "resnet", "vit", "bert", "gpt2", "llama", "exaone_moe",
    "kimi_linear", "brumby",
)


def register_model(name: str):
    def deco(factory):
        MODELS[name] = factory
        return factory

    return deco


def _load_families() -> None:
    for mod in _FAMILY_MODULES:
        try:
            importlib.import_module(f"ml_trainer_tpu.models.{mod}")
        except ImportError:
            pass


def get_model(name: str, **kwargs) -> nn.Module:
    """Build a registered model.  ``precision=`` (a policy name like
    ``'bf16'`` or a ``precision.Precision``) threads the policy's compute
    dtype onto the module's ``dtype`` knob for the families that carry
    one (the transformer zoo computes activations in ``dtype`` while
    params stay fp32 — exactly the mixed-precision split); families
    without a ``dtype`` field (mlmodel/resnet) ignore it here and rely
    on the Trainer's generic cast-at-apply instead."""
    _load_families()
    precision = kwargs.pop("precision", None)
    if precision is not None and "dtype" not in kwargs:
        from ml_trainer_tpu.precision import resolve_precision

        policy = resolve_precision(precision)
        if policy.active:
            kwargs["dtype"] = policy.compute
    try:
        factory = MODELS[name]
    except KeyError:
        raise ValueError(
            f"Unknown model {name!r}; expected one of {sorted(MODELS)}"
        ) from None
    try:
        return factory(**kwargs)
    except TypeError:
        if "dtype" in kwargs and precision is not None:
            # Family without a dtype knob: drop the threaded compute dtype
            # (the Trainer-level cast covers these models).
            kwargs.pop("dtype")
            return factory(**kwargs)
        raise


def available_models():
    _load_families()
    return sorted(MODELS)


def suggested_draft(name: str, **kwargs) -> nn.Module:
    """Build the registered draft-model partner of target ``name`` (for
    speculative decoding).  Raises ``ValueError`` when no pairing is
    registered — callers should then fall back to the model-free n-gram
    drafter rather than guess a vocabulary-incompatible model."""
    if name not in DRAFT_PAIRS:
        raise ValueError(
            f"no draft model registered for {name!r} "
            f"(known pairs: {sorted(DRAFT_PAIRS)}); use the n-gram "
            "lookup drafter instead"
        )
    return get_model(DRAFT_PAIRS[name], **kwargs)
