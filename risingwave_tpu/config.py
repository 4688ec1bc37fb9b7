"""Layered configuration — the RwConfig analogue.

Reference: src/common/src/config.rs:138 (``RwConfig { server,
streaming, storage, ... }``, TOML + serde defaults + an
``unrecognized`` capture) and src/common/src/system_param/mod.rs:77
(cluster-wide MUTABLE system params: ``barrier_interval_ms``,
``checkpoint_frequency``).

Layering (config.rs order): dataclass defaults <- TOML file <-
explicit overrides. Unknown TOML keys are collected, not fatal —
matching the reference's forward-compatible `#[serde(default)]` +
unrecognized-capture pattern.
"""

from __future__ import annotations

import os
import tomllib
from dataclasses import dataclass, field, fields
from typing import Any, Dict, Optional


def env_float(name: str, default: float) -> float:
    """An ``RW_*`` float from the environment; ``default`` when unset
    or unparsable."""
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


@dataclass
class StreamingConfig:
    """config.rs:546 StreamingConfig (the knobs our runtime consumes)."""

    chunk_capacity: int = 4096  # fixed chunk shape (stream_chunk size)
    in_flight_checkpoints: int = 8  # async upload lane depth
    # rwlint at CREATE-MV time (analysis/): True turns error-severity
    # diagnostics into DDL-time failures instead of runtime corruption.
    # Env escape hatch: RW_STRICT_LINT=0 (SqlSession reads it when the
    # session is built without an explicit setting).
    strict_lint: bool = True


@dataclass
class StorageConfig:
    """config.rs:631 StorageConfig subset."""

    object_store_root: str = "./rw_state"
    compact_at: int = 8  # SSTs per table before full-merge compaction
    bloom_bits_per_key: int = 10


@dataclass
class SystemParams:
    """Mutable cluster params (system_param/mod.rs:77-78)."""

    barrier_interval_ms: int = 1000
    checkpoint_frequency: int = 1


@dataclass
class ResilienceConfig:
    """Transient-fault knobs at the durability boundary (reference:
    ObjectStoreConfig's retry/timeout block, src/object_store/). These
    feed ``resilience.RetryPolicy`` / ``CircuitBreaker`` as the
    baseline; a SET ``RW_RETRY_*`` / ``RW_BREAKER_*`` env knob wins
    over the config (the operator's no-restart/no-file escape hatch).
    Defaults mirror the env defaults."""

    retry_max_attempts: int = 8
    retry_base_backoff_ms: int = 50
    retry_max_backoff_ms: int = 2000
    retry_deadline_s: float = 30.0
    breaker_threshold: int = 5
    breaker_cooldown_s: float = 5.0


@dataclass
class BlackboxConfig:
    """Black-box flight recorder + device-wedge sentinel knobs
    (blackbox.py). The in-memory ring is always on (``enabled``
    disables even that); ``dir`` arms the crash-surviving JSONL
    segment persistence with a bounded fsync cadence; ``sentinel``
    starts the heartbeat watchdog that converts a wedged device into a
    structured ``DeviceWedged`` + ``WEDGE_*.json`` forensic bundle.
    Env knobs (RW_BLACKBOX, RW_BLACKBOX_DIR, RW_BLACKBOX_RING,
    RW_BLACKBOX_FSYNC_S, RW_BLACKBOX_SEGMENT_MAX,
    RW_BLACKBOX_SENTINEL, RW_BLACKBOX_HEARTBEAT_S, RW_BLACKBOX_SLOW_MS,
    RW_BLACKBOX_DEADLINE_S) win over the file."""

    enabled: bool = True
    dir: str = ""  # "" = ring only, no disk persistence
    ring_barriers: int = 256
    fsync_interval_s: float = 2.0
    segment_max_bytes: int = 8_000_000
    sentinel: bool = False
    sentinel_interval_s: float = 5.0
    sentinel_slow_ms: float = 1000.0
    sentinel_deadline_s: float = 20.0


@dataclass
class RwConfig:
    streaming: StreamingConfig = field(default_factory=StreamingConfig)
    storage: StorageConfig = field(default_factory=StorageConfig)
    system: SystemParams = field(default_factory=SystemParams)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    blackbox: BlackboxConfig = field(default_factory=BlackboxConfig)
    unrecognized: Dict[str, Any] = field(default_factory=dict)


def _apply(section_obj, values: Dict[str, Any], unrecognized: Dict[str, Any], prefix: str):
    known = {f.name for f in fields(section_obj)}
    for k, v in values.items():
        if k in known:
            setattr(section_obj, k, v)
        else:
            unrecognized[f"{prefix}.{k}"] = v


def load_config(
    path: Optional[str] = None, overrides: Optional[Dict[str, Any]] = None
) -> RwConfig:
    """TOML file (optional) + dotted-path overrides, e.g.
    ``{"system.barrier_interval_ms": 250}``."""
    cfg = RwConfig()
    if path is not None:
        with open(path, "rb") as f:
            data = tomllib.load(f)
        for section in (
            "streaming", "storage", "system", "resilience", "blackbox",
        ):
            if section in data:
                _apply(
                    getattr(cfg, section), data.pop(section),
                    cfg.unrecognized, section,
                )
        for k, v in data.items():
            cfg.unrecognized[k] = v
    for dotted, v in (overrides or {}).items():
        section, _, key = dotted.partition(".")
        obj = getattr(cfg, section, None)
        if obj is None or not hasattr(obj, key):
            cfg.unrecognized[dotted] = v
        else:
            setattr(obj, key, v)
    return cfg


def select_device(device: str):
    """Bind this process to the backend asked for (``"tpu"`` or
    ``"cpu"``) and fail at start when jax found another one — an entry
    point never falls back to a backend nobody asked for. Names the
    platform and ``device_kind`` it runs on (on stderr: stdout belongs
    to the caller's protocol); returns the first device."""
    import sys

    import jax

    if device == "cpu":
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    if dev.platform != device:
        raise SystemExit(
            f"device {device!r} was asked for, jax found platform="
            f"{dev.platform!r} (device_kind {dev.device_kind!r})"
        )
    print(
        f"platform={dev.platform} device_kind={dev.device_kind!r} "
        f"device_count={len(jax.devices())} jax={jax.__version__}",
        file=sys.stderr,
        flush=True,
    )
    return dev


# the one default location: fixed under the checkout, because the path
# is part of what makes a cache entry findable again — nothing here may
# come from the environment, a pid or the clock
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent XLA compilation cache so identical
    compiles re-load across processes (``serve``, ``compute-node``,
    ``bench.py`` children, ``chip_smoke.py`` and the tests all call
    this one function). Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax
    reads it on its own and no directory is set here; otherwise the
    cache is ``<checkout>/.jax_cache`` itself. jax keys every entry by
    backend, device kind and XLA flags, so one flat directory serves
    every context. The environment is never written. Returns the
    directory in effect."""
    import re

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR
        )
    # An instruction's named scope and source line are metadata, and
    # jax keeps metadata out of a cache entry's key unless told: a
    # program whose text stood while its scopes were written (or its
    # file edited) would load with the names it was first compiled
    # with, and ``trace.program_ops`` would read those off it. So the
    # key takes the metadata in, made the same wherever the checkout
    # lies (file names from its root on) and whoever called first (a
    # location is its innermost frame, not the ten above it).
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update(
        "jax_hlo_source_file_canonicalization_regex",
        re.escape(os.path.dirname(DEFAULT_COMPILE_CACHE_DIR) + os.sep),
    )
    # (the limit, not ``jax_include_full_tracebacks_in_locations``:
    # without full tracebacks jax 0.9 drops the scopes a called program
    # opens from its operations' names)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    return jax.config.jax_compilation_cache_dir
