"""mxtpu — a TPU-native deep-learning framework with the capabilities of
Apache MXNet v1.x (reference: abhinavs95/incubator-mxnet).

Not a port: the compute path is jax/XLA (ops are HLO lowering rules, the
``hybridize()`` JIT traces into single XLA executables, distribution is
SPMD sharding with XLA collectives over ICI/DCN), with Pallas kernels for
fused hot ops.  See SURVEY.md for the reference structural analysis and
the layer-by-layer mapping.

Top-level namespace parity with ``import mxnet as mx``:
  mx.nd, mx.sym, mx.autograd, mx.gluon, mx.context/cpu/gpu/tpu, mx.random,
  mx.optimizer, mx.metric, mx.init(ializer), mx.io, mx.kvstore, mx.mod,
  mx.profiler, mx.test_utils …
"""
__version__ = "0.1.0"


def _place_compile_cache():
    """THE one place JAX's persistent compilation cache is given a
    directory.  ``JAX_COMPILATION_CACHE_DIR`` (which JAX reads into
    its own config) or a directory already configured wins and is
    left alone; otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout — a fixed path, because the path is part of
    what a later process must repeat to hit."""
    import os
    import jax
    if jax.config.jax_compilation_cache_dir:
        return
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"))


_place_compile_cache()

from . import base
from .base import MXNetError
from .context import (Context, cpu, gpu, tpu, cpu_pinned, cpu_shared,
                      current_context, num_gpus, num_tpus)
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import random
from .ndarray.random import seed  # noqa: F401  (mx.random.seed alias)

# Subsystems are imported lazily where heavy; these are light.
from . import ops


def __getattr__(name):
    # Lazy imports so `import mxtpu` stays fast and circular imports are
    # avoided while the package grows.
    import importlib
    lazy = {
        "sym": ".symbol", "symbol": ".symbol",
        "gluon": ".gluon",
        "optimizer": ".optimizer",
        "metric": ".metric",
        "init": ".initializer", "initializer": ".initializer",
        "io": ".io",
        "image": ".image",
        "kvstore": ".kvstore", "kv": ".kvstore",
        "mod": ".module", "module": ".module",
        "profiler": ".profiler",
        "test_utils": ".test_utils",
        "recordio": ".recordio",
        "callback": ".callback",
        "monitor": ".monitor",
        "visualization": ".visualization", "viz": ".visualization",
        "lr_scheduler": ".optimizer.lr_scheduler",
        "executor": ".executor",
        "engine": ".engine",
        "model": ".model",
        "parallel": ".parallel",
        "kernels": ".kernels",
        "models": ".models",
        "serving": ".serving",
        "operator": ".operator",
        "rtc": ".rtc",
        "contrib": ".contrib",
        "util": ".utils",
        "utils": ".utils",
        "rnn": ".rnn",
    }
    if name in lazy:
        mod = importlib.import_module(lazy[name], __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'mxtpu' has no attribute {name!r}")
