"""A second architecture for the tests, by new files alone: a configuration
written with another family's keys (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``max_position_embeddings``), whose reference hands
GPT-2's arithmetic back under the contract that
``benchmark/references/gpt2.py`` states.

The arithmetic is a second copy of that file's source, executed under this
module's own name: ``test_second_architecture.py`` patches every function of
``benchmark.references.gpt2`` to raise, so a call that reached the
architecture by anything but the configuration's ``"reference"`` key fails
there, while these functions, which are other objects, still answer.
"""

import importlib.util
import os

from benchmark import harness

_spec = importlib.util.spec_from_file_location(
    __name__ + "_arithmetic",
    os.path.join(harness.HERE, "references", "gpt2.py"))
_arithmetic = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_arithmetic)

make_weights = _arithmetic.make_weights
served_token_gaps = _arithmetic.served_token_gaps
train_steps = _arithmetic.train_steps


def sizes_of(config: dict) -> dict:
    return {
        "vocab": int(config["vocab_size"]),
        "positions": int(config["max_position_embeddings"]),
        "width": int(config["hidden_size"]),
        "layers": int(config["num_hidden_layers"]),
        "heads": int(config["num_attention_heads"]),
    }
