"""The work counts of the tests' second architecture
(``tiny/references/other.py``): GPT-2's counts under the contract that
``benchmark/work/gpt2.py`` states, from a second copy of that file's source,
so that they answer while every function of ``benchmark.work.gpt2`` is
patched to raise."""

import importlib.util
import os

from benchmark import harness

_spec = importlib.util.spec_from_file_location(
    __name__ + "_counts", os.path.join(harness.HERE, "work", "gpt2.py"))
_counts = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_counts)

prefill_flops = _counts.prefill_flops
decode_flops = _counts.decode_flops
train_flops_per_token = _counts.train_flops_per_token
flash_train = _counts.flash_train
