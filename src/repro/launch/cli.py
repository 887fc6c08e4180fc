"""Shared CLI surface for the launch tools (DESIGN.md §15).

``train`` and ``serve`` expose the same engine/numerics/cluster flags;
this module defines them once so the two parsers cannot drift, and turns
parsed args into a :class:`~repro.core.options.SessionOptions` in one
place — the options object then applies the documented resolution order
(explicit > ``REPRO_*`` env > default) itself.
"""
from __future__ import annotations

import argparse
import os

import jax

from ..core.options import SessionOptions

#: The checkout this package runs from (``<checkout>/src/repro/launch``).
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache across processes; returns
    its directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it
    is (JAX reads it itself); otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (the path is part of every entry's key, so
    it must not move between runs).  Entry points call this from their
    ``main``; importing a module never does."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def add_model_options(ap: argparse.ArgumentParser, *, arch: str
                      ) -> argparse.ArgumentParser:
    """--arch / --smoke: which model, at published widths unless --smoke."""
    ap.add_argument("--arch", default=arch)
    ap.add_argument("--smoke", action="store_true",
                    help="run the architecture's reduced smoke preset "
                         "(<= 2 layers, small widths) instead of its "
                         "published widths")
    return ap


def add_engine_options(ap: argparse.ArgumentParser,
                       *, numerics_default: str = "fast"
                       ) -> argparse.ArgumentParser:
    """--engine / --numerics / --backend: how a step executes locally."""
    ap.add_argument("--engine", choices=("jit", "graph"), default="jit",
                    help="jit: lowered+jitted step; graph: eager Session.run "
                         "through the cached Executable (DESIGN.md §5)")
    ap.add_argument("--numerics", choices=("fast", "strict"),
                    default=numerics_default,
                    help="graph-engine fused-region numerics (DESIGN.md §9): "
                         "fast compiles regions at full XLA optimization "
                         "under the CI-enforced tolerance contract; strict "
                         "restores fused==unfused bit-parity")
    ap.add_argument("--backend", default=None, metavar="NAME",
                    help="kernel backend for fused regions (e.g. pallas; "
                         "DESIGN.md §12) — default resolves "
                         "REPRO_KERNEL_BACKEND, then 'generic'")
    return ap


def add_cluster_options(ap: argparse.ArgumentParser,
                        *, replication: bool = False,
                        standby: bool = False) -> argparse.ArgumentParser:
    """--cluster (and friends): where a step executes (DESIGN.md §11)."""
    ap.add_argument("--cluster", default=None, metavar="HOST:PORT,...",
                    help="run over this worker pool (one `python -m "
                         "repro.distrib.worker` process per endpoint; "
                         "DESIGN.md §11)")
    if standby:
        ap.add_argument("--standby", default=None, metavar="HOST:PORT,...",
                        help="spare workers for §13 partial re-placement: a "
                             "dead task's subgraph re-places onto the first "
                             "free standby (survivors keep live state) before "
                             "the whole-pool checkpoint restart is considered")
    if replication:
        ap.add_argument("--replicas", type=int, default=1, metavar="N",
                        help="data-parallel replicas of the train step over "
                             "the --cluster pool (DESIGN.md §15)")
        ap.add_argument("--mode", choices=("sync", "async"), default="sync",
                        help="gradient aggregation across replicas: sync = "
                             "barrier step with tree-reduced mean gradients; "
                             "async = parameter-server applies with no "
                             "barrier (DESIGN.md §15)")
    return ap


def add_obs_options(ap: argparse.ArgumentParser,
                    *, summary: bool = False) -> argparse.ArgumentParser:
    """--trace-dir / --metrics-every (and --summary-dir for train): the
    §16 observability surface — distributed EEG traces, periodic metrics
    registry dumps, §9.1 scalar summaries."""
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="write a merged Chrome-trace/Perfetto JSON of the "
                         "run there (§16 distributed EEG; also REPRO_TRACE). "
                         "Unset = tracing fully off, zero per-op cost")
    ap.add_argument("--metrics-every", type=int, default=0, metavar="N",
                    help="every N steps (or requests), print a snapshot of "
                         "the §16.4 metrics registry (0 = never)")
    if summary:
        ap.add_argument("--summary-dir", default=None, metavar="DIR",
                        help="append per-step scalar summaries (loss, "
                             "tokens/sec) as JSONL events there (§9.1; "
                             "read back with repro.tools.summary.read_events)")
    return ap


def session_options_from_args(args: argparse.Namespace,
                              **overrides) -> SessionOptions:
    """A SessionOptions carrying every session-relevant flag the parser
    saw.  Only explicitly-present args are forwarded, so flags a tool did
    not register (or that stayed None) fall through to the env/default
    tiers of the options resolution order."""
    kw = {}
    for field in ("numerics", "backend", "standby"):
        v = getattr(args, field, None)
        if v is not None:
            kw[field] = v
    if getattr(args, "trace_dir", None):
        kw["trace_dir"] = args.trace_dir
    if getattr(args, "cluster", None):
        kw["cluster"] = args.cluster
    kw.update(overrides)
    return SessionOptions(**kw)
