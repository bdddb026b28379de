"""The device policy: one rule, applied at every process entry.

The CPU is used only when ``JAX_PLATFORMS`` asks for it. Otherwise the
default backend must be an accelerator, and a process that did not get
one stops with an error that says so. With ``JAX_PLATFORMS`` unset JAX
itself logs a failed accelerator init and carries on with the CPU, so
the rule looks at the backend it got, not at what it asked for.

The same entry call places the persistent compilation cache (the one
site that does) and, on an accelerator, requires the native CSV parser:
a chip fed by the Python parser is the same kind of silent degrade.

A chip belongs to one process at a time. The launchers that start
several processes on one host (``--shard N``, ``fleet --hosts N``)
therefore state their children's platform in the environment they
build, and refuse where they cannot: see :func:`cpu_children_env`.
"""

from __future__ import annotations

import os
import threading
from typing import Dict

_lock = threading.Lock()
#: this process's XLA compilations since require_backend(): requests,
#: seconds inside them, and how many the persistent cache answered
_compiles = {"count": 0, "seconds": 0.0, "cache_hits": 0}
_counting = False
#: the most devices any program of this process ran on
_devices_used = 1


class DeviceUnavailable(RuntimeError):
    """The process would run on a device its caller did not ask for."""


def checkout_root() -> str:
    """The directory that holds the ``avenir_tpu`` package."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def requested_platform() -> str:
    """The first platform ``JAX_PLATFORMS`` names, as JAX read it ('' when
    unset). Read from the config, so a test harness that pinned the
    platform with ``jax.config.update`` counts as having asked."""
    import jax

    return (jax.config.jax_platforms or "").split(",")[0].strip().lower()


def place_compile_cache() -> str:
    """Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it and nothing
    is set in code; where it is not, the cache lives in
    ``<checkout>/.jax_cache``. The directory is part of the cache key's
    stability, so it is never a temporary name, a pid or a time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        import jax

        path = os.path.join(checkout_root(), ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def _on_compile_seconds(event: str, seconds: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        with _lock:
            _compiles["count"] += 1
            _compiles["seconds"] += seconds


def _on_compile_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        with _lock:
            _compiles["cache_hits"] += 1


def _count_compiles() -> None:
    """Listen to JAX's own compile events, once per process."""
    global _counting
    from jax import monitoring

    with _lock:
        if _counting:
            return
        _counting = True
    monitoring.register_event_duration_secs_listener(_on_compile_seconds)
    monitoring.register_event_listener(_on_compile_event)


def job_mesh():
    """The mesh of a job that has a route over several chips: one data
    axis over every device this process sees, or nothing where it sees
    one, and the job is then what it is on one chip. No property, flag or
    variable chooses: a user who wants fewer chips hides them from the
    runtime. Built once a job, on the job's thread."""
    import jax

    devs = jax.local_devices()
    if len(devs) < 2:
        return None
    from avenir_tpu.parallel.mesh import data_mesh

    return data_mesh(devs)


def note_devices_used(count: int) -> None:
    """A program of this process ran on `count` devices."""
    global _devices_used
    with _lock:
        _devices_used = max(_devices_used, count)


def device_report() -> Dict:
    """What this process runs on, as JAX reports it, with its compile
    counts and its CSV parser — the row `/healthz` and chip_smoke.py
    print, so that no caller has to infer the device from the outside.
    `devices_used` is the most devices any program of the process ran
    on: 1 until a job took a route over a mesh (`job_mesh`)."""
    import jax

    from avenir_tpu.native.ingest import native_available

    devs = jax.devices()
    with _lock:
        compiles = dict(_compiles)
        used = _devices_used
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs),
            "devices_used": used,
            "xla_compiles": compiles["count"],
            "compile_s": round(compiles["seconds"], 3),
            "compile_cache_hits": compiles["cache_hits"],
            "parser": "native" if native_available() else "python"}


def require_backend() -> str:
    """Call first in every process that computes (``run_from_cli``,
    ``serve_main``, the fleet's hosts through it, ``dist/worker.py``).
    Returns the platform the process runs on; raises DeviceUnavailable
    when that is the CPU and nobody asked for the CPU."""
    import jax

    place_compile_cache()
    # A Pallas kernel's payload carries the locations of its trace and is
    # part of its cache key. With full tracebacks (JAX's default) that is
    # up to ten frames of whoever called, so the CLI and the server each
    # compiled the same kernel (150 s each on the chip, PR 21); the
    # innermost frame alone is the kernel's own line.
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    _count_compiles()
    platform = jax.default_backend()
    from avenir_tpu.native import ingest

    if requested_platform() == "cpu":
        ingest.native_available()   # settle the parser now, not in /healthz
        return platform
    if platform == "cpu":
        raise DeviceUnavailable(
            "no accelerator: JAX initialised the CPU backend although "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r} did not "
            "ask for it (the accelerator's own init error is in the log "
            "above). Set JAX_PLATFORMS=cpu to run on the CPU on purpose.")
    ingest.require_native()
    return platform


def cpu_children_env(env: Dict[str, str], launcher: str) -> Dict[str, str]:
    """State the platform of the processes `launcher` is about to start,
    in the environment it builds for them. Several processes on one host
    cannot share its accelerator (the first takes it; the rest fail or
    fold on the CPU beside it), and this package has no launcher that
    gives each child a chip of its own, so the only placement it can
    state is the CPU — and only when the CPU was asked for."""
    asked = (env.get("JAX_PLATFORMS") or requested_platform())
    if asked.split(",")[0].strip().lower() != "cpu":
        raise DeviceUnavailable(
            f"{launcher} starts several processes on this host, and an "
            "accelerator belongs to one process at a time. Set "
            "JAX_PLATFORMS=cpu to run them on the host's cores, or run "
            "the job in one process, which is what drives the chip.")
    env["JAX_PLATFORMS"] = "cpu"
    return env
