"""Kernel-path selection (the `BFSConfig(expand=...)` / `BFSConfig(fold=...)`
rules; DESIGN.md sec. 9 + 10).

Deliberately Pallas-free: the engines call `resolve_expand_path` and
`resolve_fold_path` on EVERY construction -- including "reference" ones on
installs without jax.experimental.pallas -- so the selection logic must
import without it.  The kernels themselves live in `repro.kernels.expand` /
`repro.kernels.fold` and are only imported once a non-reference path is
selected.

All knobs share one spelling set ("reference" | "pallas" |
"pallas-interpret" | "auto") and one resolution rule; they differ only in
the environment override that CI matrix legs use to force a path
process-wide (REPRO_EXPAND for the expand scan, REPRO_FOLD for the fold
pipeline, REPRO_BOTTOMUP for the bottom-up parent search).

The rule:

  * "auto" takes the environment override when one is set, and otherwise
    the platform's entry in `AUTO_PATH`.  On TPU that is "reference": the
    TPU compiler refuses every Pallas kernel here (DESIGN.md sec. 9-11
    list each refusal), so the compiled default is the jnp scan.
  * "pallas" is taken as asked on every platform; where the compiler
    refuses a kernel, its own error reaches the caller.
  * "pallas-interpret" runs the kernel bodies in the Pallas interpreter and
    is accepted on CPU only -- an interpreter on an accelerator would pass
    for the kernel while measuring something else.
"""
from __future__ import annotations

import os

EXPAND_PATHS = ("reference", "pallas", "pallas-interpret")
EXPAND_ENV = "REPRO_EXPAND"

FOLD_PATHS = EXPAND_PATHS
FOLD_ENV = "REPRO_FOLD"

BOTTOMUP_PATHS = EXPAND_PATHS
BOTTOMUP_ENV = "REPRO_BOTTOMUP"

# what "auto" resolves to per backend (absent: "pallas")
AUTO_PATH = {"cpu": "reference", "tpu": "reference"}


def _platform(platform: str | None) -> str:
    if platform is None:
        import jax
        platform = jax.default_backend()
    return platform


def _resolve(spec, *, env: str, knob: str, platform: str | None) -> str:
    if spec is None:
        spec = "auto"
    if spec == "auto":
        override = os.environ.get(env, "").strip().lower()
        if override and override != "auto":
            if override not in EXPAND_PATHS:
                raise ValueError(
                    f"{env}={override!r}: expected one of {EXPAND_PATHS} "
                    f"or 'auto'")
            spec, knob = override, env
        else:
            return AUTO_PATH.get(_platform(platform), "pallas")
    if spec not in EXPAND_PATHS:
        raise ValueError(
            f"{knob}={spec!r}: expected one of {EXPAND_PATHS + ('auto',)}")
    if spec == "pallas-interpret" and _platform(platform) != "cpu":
        raise ValueError(
            f"{knob}='pallas-interpret' runs the Pallas interpreter, which "
            f"is for CPU only; on {_platform(platform)!r} use 'reference' "
            f"or 'pallas'")
    return spec


def resolve_expand_path(spec="auto", *, platform: str | None = None) -> str:
    """Concretise an expand-path spelling.

    spec: "reference" | "pallas" are themselves; "pallas-interpret" is
    itself on CPU and refused elsewhere; "auto" (or None) consults the
    REPRO_EXPAND environment variable first (so CI matrix legs force the
    kernel path process-wide) and otherwise takes `AUTO_PATH` for the
    platform ("reference" on CPU and TPU).
    """
    return _resolve(spec, env=EXPAND_ENV, knob="expand", platform=platform)


def resolve_fold_path(spec="auto", *, platform: str | None = None) -> str:
    """Concretise a fold-path spelling (same rules, REPRO_FOLD override)."""
    return _resolve(spec, env=FOLD_ENV, knob="fold", platform=platform)


def resolve_bottomup_path(spec="auto", *, platform: str | None = None) -> str:
    """Concretise a bottom-up-path spelling (same rules, REPRO_BOTTOMUP
    override)."""
    return _resolve(spec, env=BOTTOMUP_ENV, knob="bottomup",
                    platform=platform)
