"""Computation-environment hygiene: platform, precision, XLA flags,
compile cache.

The "hardware truth" prerequisite (ROADMAP): before any benchmark or
training run touches a device, pin the platform/precision/XLA-flag state
*explicitly* and record exactly what was resolved, so a BENCH history
entry measured on one box is comparable with the next (idiom from the
bayespec ``set_platform``/x64 config helpers and the olmax XLA-flag
run.sh — see SNIPPETS.md).

Everything here is import-safe before jax initializes its backend (only
env vars and ``jax.config`` updates); call :func:`configure` at the top
of a driver's ``main()`` and pass :func:`resolved_state` into the run
manifest (``repro.obs.sink.run_manifest`` does the latter
automatically).

Environment overrides (all optional): ``REPRO_PLATFORM`` (cpu|tpu),
``REPRO_X64`` (0|1), ``REPRO_HOST_DEVICES`` (int) — the knobs CI and
benchmark boxes set without code changes — and
``JAX_COMPILATION_CACHE_DIR``, which jax reads itself: when it is set the
persistent compile cache lives there, otherwise at the fixed, git-ignored
``<checkout>/.jax_cache`` (a fixed path, because the path is part of the
cache's key).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Any, Dict, Optional

import jax

# <checkout>/.jax_cache: src/repro/launch/env.py -> four levels up
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / '.jax_cache')

# what configure() resolved this process to — the manifest's 'env' block
_STATE: Dict[str, Any] = {'configured': False}


def set_platform(platform: Optional[str] = None) -> Optional[str]:
    """Pin the backend ('cpu' | 'tpu').  Only takes effect before the
    first device use; ``None`` leaves jax's own resolution in place (and
    records that)."""
    platform = platform or os.environ.get('REPRO_PLATFORM') or None
    if platform:
        jax.config.update('jax_platform_name', platform)
    return platform


def set_compile_cache() -> str:
    """Place jax's persistent compilation cache and return its directory.
    ``JAX_COMPILATION_CACHE_DIR``, when set, is left to jax (no other
    directory is set in code); otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`.  Must run before the first compile."""
    env_dir = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env_dir:
        return env_dir
    jax.config.update('jax_compilation_cache_dir', CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


def enable_x64(use_x64: Optional[bool] = None) -> bool:
    """Default-dtype precision.  The repo's allocation closed forms
    overflow f32 and re-enter x64 locally (``with jax.enable_x64():``);
    this global knob is for whole-process x64 runs (JAX_ENABLE_X64=1 /
    REPRO_X64=1 honored when unset)."""
    if use_x64 is None:
        use_x64 = os.environ.get(
            'REPRO_X64', os.environ.get('JAX_ENABLE_X64', '0')) == '1'
    jax.config.update('jax_enable_x64', bool(use_x64))
    return bool(use_x64)


def set_host_device_count(n: Optional[int] = None) -> Optional[int]:
    """Force N host-platform devices (the CPU-mesh trick every sharded
    test/bench uses).  Must run before backend init; no-op if the flag
    is already pinned (e.g. by CI's env)."""
    if n is None:
        raw = os.environ.get('REPRO_HOST_DEVICES')
        n = int(raw) if raw else None
    if n:
        flags = os.environ.get('XLA_FLAGS', '')
        if 'xla_force_host_platform_device_count' not in flags:
            os.environ['XLA_FLAGS'] = (
                f'{flags} --xla_force_host_platform_device_count={n}'
            ).strip()
    return n


def configure(platform: Optional[str] = None,
              use_x64: Optional[bool] = None,
              host_device_count: Optional[int] = None) -> Dict[str, Any]:
    """Apply the full hygiene pass and record what was resolved.  Safe to
    call more than once (later calls re-record)."""
    _STATE.update(
        configured=True,
        platform=set_platform(platform),
        x64=enable_x64(use_x64),
        host_device_count=set_host_device_count(host_device_count),
        compile_cache_dir=set_compile_cache(),
        xla_flags=os.environ.get('XLA_FLAGS', ''),
        jax_platforms=os.environ.get('JAX_PLATFORMS', ''),
    )
    return dict(_STATE)


def resolved_state() -> Dict[str, Any]:
    """The recorded configure() outcome plus the live backend view —
    what the run manifest embeds.  Reading the live view initializes the
    backend, so manifests report the environment actually used."""
    state = dict(_STATE)
    state.update(
        backend=jax.default_backend(),
        device_count=jax.device_count(),
        x64_enabled=bool(jax.config.jax_enable_x64),
        xla_flags=os.environ.get('XLA_FLAGS', ''),
        jax_platforms=os.environ.get('JAX_PLATFORMS', ''),
    )
    return state
