"""The system under test, as the benchmark drives it.

Everything the benchmark takes from the program goes through here: the
model configuration object, the jitted training step exactly as
``repro.launch.train.train(engine="jit")`` builds it, its optimizer state,
the input prefetcher, the compile cache, and the continuous batcher.
"""
from __future__ import annotations

import os
import sys
from typing import Any, Dict, Tuple

from . import arch
from .spec import CHECKOUT

SRC = os.path.join(CHECKOUT, "src")


class ProgramMissing(RuntimeError):
    """The checkout holds no ``src/repro`` package."""


def import_program() -> None:
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise ProgramMissing(f"no repro package under {SRC}")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


def enable_compile_cache(path: str = "") -> str:
    """JAX's persistent compilation cache at ``path``, by default the fixed
    ``<checkout>/.jax_cache``, handed to the program through the variable
    its own ``enable_compile_cache`` reads, whatever the environment set."""
    import jax

    from repro.launch.cli import enable_compile_cache as enable

    path = path or os.path.join(CHECKOUT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    enable()
    # every program of a cell, however quick to compile, comes from the
    # cache after the first run, so set-up does the same work each time;
    # no eviction, whose bookkeeping fails on an entry that another thread
    # is still writing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def model_config(c: Dict[str, Any]):
    """The program's ``ModelConfig`` for configuration ``c``, as its
    architecture's module builds it."""
    return arch.of(c).program_config(c)


def check_layout(model, weights) -> None:
    """The benchmark's weight tree has the program's structure and shapes."""
    import jax

    want = model.abstract_params()
    got = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), weights)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"weight layout differs from the program's: "
                         f"{jax.tree.structure(want)} vs {jax.tree.structure(got)}")


def train_step(c: Dict[str, Any], batch: int, seq: int,
               compute_dtype: str = "float32") -> Tuple[Any, Any]:
    """(StepBundle, jitted step with donated state) as ``train()`` builds
    them: ``TRAIN_HPARAMS``, the configuration's learning rate.
    ``compute_dtype="bfloat16"`` switches on the program's own bf16 path
    (the precision control)."""
    import jax
    import jax.numpy as jnp

    from repro.launch.steps import build_train_step
    from repro.launch.train import TRAIN_HPARAMS
    from repro.models.api import Shape

    hp = dict(TRAIN_HPARAMS)
    if compute_dtype != "float32":
        hp["compute_dtype"] = jnp.dtype(compute_dtype)
    sb = build_train_step(model_config(c), Shape("custom", seq, batch, "train"),
                          lr=c["deployment"]["optimizer"]["lr"],
                          hparam_overrides=hp)
    return sb, jax.jit(sb.fn, donate_argnums=(1,))


def optimizer_init(params):
    from repro.optim import adamw_init

    return adamw_init(params)


def prefetcher(source, capacity: int = 4):
    from repro.data import Prefetcher

    return Prefetcher(source, capacity=capacity).start()


def batcher(c: Dict[str, Any], params):
    from repro.models.api import Model
    from repro.serving import ContinuousBatcher

    dep = c["deployment"]
    model = Model.for_config(model_config(c))
    check_layout(model, params)
    return ContinuousBatcher(model, params, n_slots=int(dep["n_slots"]),
                             max_seq=int(dep["max_seq"]))


def request(rid: int, prompt, max_new_tokens: int):
    from repro.serving.batcher import Request

    return Request(rid=rid, prompt=list(prompt), max_new_tokens=max_new_tokens,
                   temperature=0.0, eos_id=None)
