"""Process-level XLA settings for entry points.

* Forced multi-device CPU runs.  XLA fixes the host-platform device count
  when jax initializes, so any harness that wants to compare device
  counts (the sharded-fleet scaling sweep, the 4-virtual-device parity
  test) must re-exec itself with
  ``--xla_force_host_platform_device_count=N`` set *before* import.  This
  is the one shared builder for that environment, so the flag-rewrite
  rules can't drift between callers.
* The persistent compilation cache (``enable_compile_cache``), turned on
  by entry points — never by library code or tests.
"""
from __future__ import annotations

import os
import re
from pathlib import Path
from typing import Dict, Optional

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# fixed, git-ignored cache directory at the root of the checkout
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    at import, and this leaves it so and sets no other directory.
    Otherwise the cache goes to ``REPO_CACHE_DIR``: one fixed path, never
    built from a temporary name, a PID or the time, so a later process in
    the same checkout finds what an earlier one compiled.
    """
    import jax

    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)


def force_host_devices_here(n_devices: int) -> None:
    """Pin THIS process's ``XLA_FLAGS`` to ``n_devices`` virtual CPU devices.

    In-place sibling of ``forced_host_device_env`` for entry points that
    own their process (the dryrun CLI).  XLA reads the flag once when the
    backend initializes — the first ``jax.devices()`` / array op — so
    calling this after ``import jax`` but before any jax *use* is still
    effective.  Any pre-existing forced count is stripped first, same
    rewrite rule as the subprocess builder.
    """
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices} "
        + flags).strip()


def forced_host_device_env(n_devices: int,
                           repo_root: Optional[str] = None
                           ) -> Dict[str, str]:
    """A copy of ``os.environ`` pinned to ``n_devices`` virtual CPU devices.

    CPU-only by design: the child always gets ``JAX_PLATFORMS=cpu``.  A
    chip belongs to one process, so callers whose own backend is an
    accelerator must not start such children
    (``benchmarks/fleet_sweep.py`` refuses to).  Any pre-existing forced
    device count in ``XLA_FLAGS`` is stripped first (the parent may itself
    be a forced-device process — e.g. the CI multi-device job).
    ``repo_root``, when given, prepends its ``src``
    directory to ``PYTHONPATH`` so the child can import ``repro`` no
    matter how the parent was launched.
    """
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={n_devices} "
        + flags).strip()
    env["JAX_PLATFORMS"] = "cpu"
    if repo_root is not None:
        src = os.path.join(os.path.abspath(repo_root), "src")
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    return env
