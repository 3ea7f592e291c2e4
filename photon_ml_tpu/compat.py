"""Process start-up shared by every entry point: which device jax gave
us, where the persistent XLA compilation cache lives, and the forced
multi-device CPU mesh the test harnesses ride.

Written for the one installation the repo runs on (jax 0.9.0): call sites
use ``jax.shard_map``, ``jax.enable_x64``, ``jax.distributed.is_initialized``
and ``pallas.tpu.CompilerParams`` directly.
"""

from __future__ import annotations

import os
from typing import Callable, Optional

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: where the compile cache goes when neither the environment nor the caller
#: names a directory: fixed and inside the checkout (git-ignored). The path
#: is part of the cache's key, so it must never carry a temporary name, a
#: pid or a time — a directory that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_compilation_cache",
)


def device_summary() -> str:
    """``platform: …, device_kind: …, count: …`` of the devices jax gave
    this process — every entry point logs it once at start, so a run that
    silently landed on the CPU says so in its first lines."""
    import jax

    devs = jax.devices()
    text = (
        f"platform: {devs[0].platform}, device_kind: {devs[0].device_kind}, "
        f"count: {len(devs)}"
    )
    if jax.process_count() > 1:
        text += (
            f" (process {jax.process_index()}/{jax.process_count()}, "
            f"{jax.local_device_count()} local)"
        )
    return text


def enable_persistent_cache(path: Optional[str] = None) -> str:
    """Turn on jax's persistent XLA compilation cache and return its
    directory — THE one rule every entry point shares, in priority order:
    ``JAX_COMPILATION_CACHE_DIR`` (whoever runs the program places the
    cache; nothing in code names another directory), else ``path`` (a
    driver's ``--persistent-cache``), else
    :data:`DEFAULT_COMPILE_CACHE_DIR`.

    The two tuning knobs that default to skipping small/fast entries are
    both zeroed, because the GLMix solver sites are exactly the
    many-small-executables workload those defaults would exclude (a "warm"
    run that still recompiles every solver kernel reports zero benefit).
    """
    import jax
    from jax._src import compilation_cache

    from photon_ml_tpu.compile import overrides

    cache_dir = (
        overrides.env_read(COMPILE_CACHE_ENV)
        or path
        or DEFAULT_COMPILE_CACHE_DIR
    )
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache EVERYTHING: -1 disables the min-entry-size filter; 0 disables
    # the min-compile-seconds filter
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # jax keys the cache on the program WITHOUT its metadata unless told
    # otherwise, so a warm cache hands back whichever build compiled the
    # program first, with that build's scope names (jax.named_scope, the
    # names a trace is read by: PERF.md, "Spans, scopes and counters") or
    # with none. With the metadata in the key a trace shows the names of
    # the code that ran; the price is a recompile when a traced line moves.
    # One frame per operation, not the callers' stack: the key must not
    # depend on which line of which entry point made the first call.
    # (jax_include_full_tracebacks_in_locations=False would do that too,
    # but in jax 0.9.0 it also drops the scope names from op_name.)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    # jax LATCHES cache-used at the first compile of the process; a driver
    # that touched the device before reaching this call (device summary,
    # data placement) would silently never cache without a reset
    compilation_cache.reset_cache()
    return cache_dir


def start_up(log: Callable[[str], None], cache_arg: Optional[str] = None) -> str:
    """Every entry point's first lines: log the device jax gave the process
    and turn on the compile cache (``cache_arg`` is the driver's
    ``--persistent-cache``). Returns the cache directory."""
    log(device_summary())
    cache_dir = enable_persistent_cache(cache_arg)
    log(f"persistent XLA compilation cache: {cache_dir}")
    return cache_dir


_FORCE_CPU_FLAG = "--xla_force_host_platform_device_count"


def forced_cpu_device_count(flags: Optional[str] = None) -> Optional[int]:
    """The CPU device count forced through ``XLA_FLAGS``
    (``--xla_force_host_platform_device_count=N``), or ``None`` when the
    flag is absent or malformed. The LAST occurrence wins, matching XLA's
    own parse. Pass ``flags`` to inspect a specific string (a child
    environment under construction); the default reads the process env
    through the one overrides gate."""
    if flags is None:
        from photon_ml_tpu.compile import overrides

        flags = overrides.env_read("XLA_FLAGS", "") or ""
    count = None
    for part in flags.split():
        if part.startswith(_FORCE_CPU_FLAG + "="):
            try:
                count = int(part.split("=", 1)[1])
            except ValueError:
                return None
    return count


def backends_initialized() -> bool:
    """Whether jax has already instantiated a PJRT backend — after which
    ``XLA_FLAGS`` edits are silently ignored. Probes the backend registry
    WITHOUT initializing it."""
    from jax._src import xla_bridge

    return bool(xla_bridge._backends)


def force_cpu_devices(n: int) -> bool:
    """Arrange for the host CPU platform to expose ``n`` devices by
    pinning ``--xla_force_host_platform_device_count=n`` into
    ``XLA_FLAGS`` (the multi-device-single-host mesh the psum merge arms
    ride). XLA reads the flag exactly once, at backend instantiation, so:

      * before jax initializes: rewrite the env (replacing any prior
        occurrence of the flag) and return True;
      * after jax initializes: an env edit is a silent no-op — return
        whether the LIVE CPU backend already satisfies the request, so
        the caller knows to skip or re-exec in a fresh subprocess (the
        bench psum arm's structured ``preflight:`` skip).
    """
    if n < 1:
        raise ValueError(f"force_cpu_devices needs n >= 1, got {n}")
    if backends_initialized():
        import jax

        try:
            return len(jax.devices("cpu")) >= n
        except RuntimeError:  # no CPU platform in this process's config
            return False
    if forced_cpu_device_count() == n:
        return True
    from photon_ml_tpu.compile import overrides

    flags = overrides.env_read("XLA_FLAGS", "") or ""
    parts = [
        p for p in flags.split() if not p.startswith(_FORCE_CPU_FLAG + "=")
    ]
    parts.append(f"{_FORCE_CPU_FLAG}={n}")
    os.environ["XLA_FLAGS"] = " ".join(parts)
    return True
