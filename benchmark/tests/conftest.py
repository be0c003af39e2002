"""``pytest benchmark/tests`` — by hand, on the CPU, at tiny sizes.

Nothing here measures: a time, a rate or a share of a peak comes only
from a run on the chip.
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402

# these tiny programs have no business in the checkout's compile cache,
# which the repo's own tests share
jax.config.update("jax_enable_compilation_cache", False)

TINY = {"vocab_size": 97, "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "max_position_embeddings": 32, "type_vocab_size": 2,
        "hidden_dropout_prob": 0.1, "causal": False, "use_token_type": True}
TINY_GEN = dict(TINY, hidden_dropout_prob=0.0, causal=True,
                use_token_type=False)
TINY_TRAIN_MIX = {"kind": "train", "batch": 4, "seq": 16,
                  "optimizer": "adam", "learning_rate": 1e-3,
                  "compute_dtype": None, "check_steps": 3,
                  # held and printed as in the cell's workload file; the
                  # tiny float32 program reads 1e-5, its fp8 control 3e-3
                  # and more
                  "limits": {"loss_rel_gap": None, "grad_norm_gap": 1e-3,
                             "change_norm_gap": 1e-3,
                             "grad_norm_gap_own": None,
                             "change_norm_gap_own": None}}
