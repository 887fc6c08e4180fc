"""DESIGN.md §15 SessionOptions consolidation tests.

One object carries every Session knob with one documented resolution
order (explicit > ``REPRO_*`` env > default); the legacy per-field
``Session(...)`` kwargs keep working through a deprecation shim; the
RunSignature derives all options-dependent cache-key components from the
resolved options in one place; and the shared ``launch/cli.py`` builder
turns parsed args into the same object for train.py AND serve.py.
"""
import argparse
import dataclasses

import jax.numpy as jnp
import pytest

import repro.core.session as session_mod
from repro.core import GraphBuilder, Session
from repro.core.executable import RunSignature
from repro.core.options import SessionOptions, parse_guard
from repro.launch.cli import (add_cluster_options, add_fusion_options,
                              session_options_from_args)


def _tiny_session(**kw):
    b = GraphBuilder()
    x = b.constant(jnp.ones((2, 2)), name="x")
    out = b.add(x, x, name="out")
    return b, out, Session(b.graph, **kw)


# --- resolution order -------------------------------------------------------

def test_defaults_resolve(monkeypatch):
    for var in ("REPRO_VERIFY", "REPRO_FUSE_REGIONS", "REPRO_FUSE_NUMERICS",
                "REPRO_NUMERICS_GUARD", "REPRO_KERNEL_BACKEND"):
        monkeypatch.delenv(var, raising=False)
    o = SessionOptions().resolve()
    assert (o.verify, o.fuse_regions, o.numerics, o.backend) == (
        "warn", True, "strict", "generic")


def test_env_beats_default_and_explicit_beats_env(monkeypatch):
    monkeypatch.setenv("REPRO_FUSE_NUMERICS", "fast")
    monkeypatch.setenv("REPRO_VERIFY", "off")
    monkeypatch.setenv("REPRO_FUSE_REGIONS", "0")
    assert SessionOptions().resolve().numerics == "fast"
    assert SessionOptions().resolve().verify == "off"
    assert SessionOptions().resolve().fuse_regions is False
    o = SessionOptions(numerics="strict", verify="error",
                       fuse_regions=True).resolve()
    assert (o.numerics, o.verify, o.fuse_regions) == ("strict", "error", True)


def test_invalid_values_raise(monkeypatch):
    with pytest.raises(ValueError):
        SessionOptions(numerics="sloppy").resolve()
    with pytest.raises(ValueError):
        SessionOptions(verify="maybe").resolve()
    with pytest.raises(ValueError):
        SessionOptions(backend="cuda-classic").resolve()


def test_standby_string_splits():
    o = SessionOptions(standby="a:1, b:2,").resolve()
    assert o.standby == ("a:1", "b:2")


def test_parse_guard_policies():
    assert parse_guard(True) == (True, None)
    assert parse_guard("0") == (False, None)
    assert parse_guard("off") == (False, None)
    assert parse_guard("sample:8") == (True, 8)
    assert parse_guard(4) == (True, 4)
    with pytest.raises(ValueError):
        parse_guard("sample:0")


# --- legacy-kwarg deprecation shim -----------------------------------------

def test_legacy_kwargs_warn_once_and_fold_into_options():
    session_mod._warned_legacy_kwargs = False
    with pytest.warns(DeprecationWarning, match="SessionOptions"):
        _b, _out, sess = _tiny_session(numerics="fast", parity_guard=False,
                                       fuse_regions=True)
    assert sess.options.numerics == "fast"
    assert sess.numerics == "fast"  # mirrored attr keeps working
    assert sess.parity_guard is False
    sess.close()
    # once per process: the second legacy construction stays quiet
    import warnings

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        _b, _out, sess = _tiny_session(numerics="strict")
    sess.close()


def test_explicit_kwarg_overrides_options_field():
    session_mod._warned_legacy_kwargs = True  # silence, tested above
    _b, _out, sess = _tiny_session(
        options=SessionOptions(numerics="strict"), numerics="fast")
    assert sess.options.numerics == "fast"
    sess.close()


# --- RunSignature derives from the resolved options -------------------------

def test_run_signature_tracks_option_fields():
    session_mod._warned_legacy_kwargs = True
    b = GraphBuilder()
    x = b.constant(jnp.ones((2, 2)), name="x")
    out = b.add(x, x, name="out")
    sigs = set()
    for opts in (SessionOptions(),
                 SessionOptions(numerics="fast", parity_guard=False),
                 SessionOptions(backend="pallas"),
                 SessionOptions(fuse_regions=False),
                 SessionOptions(verify="error")):
        sess = Session(b.graph, options=opts)
        sigs.add(RunSignature.for_session(sess, (out.ref,), frozenset()))
        sess.close()
    assert len(sigs) == 5  # every flip re-keys the Executable cache


# --- launch/cli.py shared options builder -----------------------------------

def _parser(**kw):
    ap = argparse.ArgumentParser()
    add_fusion_options(ap)
    add_cluster_options(ap, **kw)
    return ap


def test_cli_roundtrip_to_options():
    args = _parser().parse_args(
        ["--numerics", "strict", "--backend", "pallas",
         "--cluster", "h:1,h:2"])
    o = session_options_from_args(args)
    assert o.numerics == "strict"
    assert o.backend == "pallas"
    assert o.cluster == "h:1,h:2"


def test_cli_absent_flags_fall_through_to_env(monkeypatch):
    args = _parser().parse_args([])  # --backend stays None
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
    o = session_options_from_args(args)
    assert o.backend is None  # unset: the options resolution order decides
    assert o.resolve().backend == "pallas"


def test_cli_replication_flags():
    args = _parser(replication=True, standby=True).parse_args(
        ["--cluster", "h:1", "--replicas", "4", "--mode", "async",
         "--standby", "h:9"])
    assert (args.replicas, args.mode) == (4, "async")
    o = session_options_from_args(args)
    assert o.resolve().standby == ("h:9",)
    # train/serve share one surface: no replication flags unless asked
    with pytest.raises(SystemExit):
        _parser().parse_args(["--replicas", "4"])


def test_cli_overrides_win():
    args = _parser().parse_args(["--numerics", "fast"])
    o = session_options_from_args(args, numerics="strict", parity_guard=False)
    assert o.numerics == "strict"
    assert o.parity_guard is False
