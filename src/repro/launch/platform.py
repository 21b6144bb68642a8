"""Named platform presets: the XLA-flag / device-tier / x64 tuning plane.

ROADMAP Open item 4's enabling half, in the spirit of bayespec's
``elisa/util/config.py`` and olmax's run scripts (SNIPPETS 1-3): every
knob that changes what a measurement *means* — latency-hiding scheduler,
async collectives, triton fusion, fake-device tiers, x64 — lives in a
named, stampable `PlatformPreset` instead of ad-hoc ``XLA_FLAGS`` exports
scattered across shells and CI yaml.

    from repro.launch import platform as pf
    pf.apply("overlap-cpu8")        # BEFORE any jax computation
    ...                             # flags now govern backend init

Rules of engagement:

* ``apply`` must run before the first jax computation — XLA reads
  ``XLA_FLAGS`` once, at lazy backend init.  (A module-level ``import
  jax`` is safe; creating the first array/device is not.)  Applying
  after init warns loudly and still records the intent, so the
  provenance stamp never lies about what was *requested* vs *active*.
* Presets MERGE with the ambient ``XLA_FLAGS`` rather than clobbering
  it: CI sets ``--xla_force_host_platform_device_count=8`` globally, and
  a preset must compose with that.  When both the environment and the
  preset force a host device count, the environment wins (the outer
  environment knows its machine; the preset is a portable request).
* The GPU scheduling flags (``--xla_gpu_enable_latency_hiding_scheduler``
  and friends) are compiled into every XLA build's DebugOptions, so they
  parse on CPU too — a CPU run under the ``overlap`` preset records the
  request and the backend simply has no async stream to use.  The real
  hazard is version skew: a flag XLA has since *removed* is FATAL at
  backend init (``parse_flags_from_env`` aborts the process), which is
  why ``_OVERLAP_FLAGS`` is pinned to spellings the repo's pinned jaxlib
  knows.  Whether async collectives actually *fired* is a separate,
  measured fact: `async_collectives_in` inspects compiled HLO for
  start/done pairs, and `benchmarks/engine_bench.py`'s ``overlap``
  section records the answer next to the timings.

Every applied preset is exposed via `active()` and stamped into
`repro.obs.RunProvenance` (``platform_preset`` / ``xla_flags``), so a
``BENCH_*.json`` number can never be read apart from the flag set that
produced it.
"""
from __future__ import annotations

import os
import warnings
from dataclasses import dataclass
from typing import Optional, Union

# the SNIPPET-1 (bayespec) GPU tuning set, modernized for jaxlib 0.9: the
# latency-hiding scheduler + pipelined all-gather are what let an
# all-gather overlap compute (XLA dropped the older
# ``--xla_gpu_enable_async_*``, ``--xla_gpu_enable_pipelined_collectives``
# and ``--xla_gpu_enable_triton_softmax_fusion`` spellings, and an unknown
# flag is FATAL at backend init — parse_flags_from_env aborts — so this set
# is pinned to flags the installed jaxlib knows; tests/test_overlap.py
# starts a backend under it); the triton gemm fusion rides along for the
# gemm-heavy ERA path
_OVERLAP_FLAGS = (
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
    "--xla_gpu_enable_pipelined_all_gather=true",
    "--xla_gpu_triton_gemm_any=True",
)

_FORCE_HOST = "--xla_force_host_platform_device_count"


@dataclass(frozen=True)
class PlatformPreset:
    """One named tuning configuration.  ``xla_flags`` merge into the
    environment; ``host_device_count`` requests an N-fake-device CPU tier
    (ignored when the ambient ``XLA_FLAGS`` already forces a count);
    ``x64`` toggles ``jax_enable_x64`` (None = leave untouched)."""
    name: str
    description: str
    xla_flags: tuple = ()
    host_device_count: Optional[int] = None
    x64: Optional[bool] = None


PRESETS = {
    "default": PlatformPreset(
        "default", "no tuning: whatever the ambient environment says"),
    "cpu8": PlatformPreset(
        "cpu8", "8 fake CPU devices: the multi-device CI tier "
        "(exercises pod-sharded collectives without an accelerator)",
        host_device_count=8),
    "overlap": PlatformPreset(
        "overlap", "latency-hiding scheduler + pipelined all-gather "
        "+ triton gemm fusion (SNIPPET-1 bayespec set): lets the "
        "pipelined exchange actually hide behind compute off-CPU",
        xla_flags=_OVERLAP_FLAGS),
    "overlap-cpu8": PlatformPreset(
        "overlap-cpu8", "the overlap flag set on the 8-fake-device CPU "
        "tier — the configuration the BENCH_engine overlap section runs",
        xla_flags=_OVERLAP_FLAGS, host_device_count=8),
    "x64": PlatformPreset(
        "x64", "double-precision mode (olmax JAX_ENABLE_X64 idiom)",
        x64=True),
}

_active: Optional[PlatformPreset] = None


def names() -> list:
    return sorted(PRESETS)


def active() -> Optional[PlatformPreset]:
    """The preset applied in this process, if any (provenance reads it)."""
    return _active


def backend_initialized() -> bool:
    """Whether jax has already materialized a backend (after which
    XLA_FLAGS edits no longer take effect).  Defensive: absent internals
    report False rather than raising."""
    try:
        import jax
        backends = getattr(
            getattr(jax, "_src", None), "xla_bridge", None)
        if backends is not None:
            return bool(getattr(backends, "_backends", None))
    except Exception:
        pass
    return False


def apply(preset: Union[str, PlatformPreset]) -> PlatformPreset:
    """Merge ``preset`` into the process environment (and jax config) and
    record it as the active preset.  Idempotent for a given preset; call
    it at the TOP of an entry point, before any jax computation."""
    global _active
    if isinstance(preset, str):
        try:
            preset = PRESETS[preset]
        except KeyError:
            raise ValueError(
                f"unknown platform preset {preset!r}; "
                f"available: {', '.join(names())}") from None
    ambient = os.environ.get("XLA_FLAGS", "")
    merged = [f for f in ambient.split() if f]
    for flag in preset.xla_flags:
        if flag not in merged:
            merged.append(flag)
    if preset.host_device_count is not None:
        if not any(f.startswith(_FORCE_HOST) for f in merged):
            merged.append(f"{_FORCE_HOST}={preset.host_device_count}")
        # else: the ambient environment already forces a count — it wins
    new_flags = " ".join(merged)
    if new_flags != ambient:
        if backend_initialized():
            warnings.warn(
                f"platform preset {preset.name!r} applied after jax "
                f"backend init: XLA_FLAGS changes will NOT take effect "
                f"in this process (apply() must run first)", stacklevel=2)
        if new_flags:
            os.environ["XLA_FLAGS"] = new_flags
    if preset.x64 is not None:
        import jax
        jax.config.update("jax_enable_x64", bool(preset.x64))
    _active = preset
    return preset


def add_args(ap) -> None:
    """Install ``--platform-preset`` on an argparse parser (the launch
    drivers and benchmarks share this flag)."""
    ap.add_argument(
        "--platform-preset", default=None, choices=names(),
        metavar="NAME",
        help="named XLA/platform tuning preset applied before backend "
             "init (merges with ambient XLA_FLAGS; stamped into "
             "provenance): " + ", ".join(names()))


def from_args(args) -> Optional[PlatformPreset]:
    """Apply the preset named by ``--platform-preset``, if any.  Call at
    the top of ``main`` — before building engines or touching devices."""
    name = getattr(args, "platform_preset", None)
    return apply(name) if name else None


# --------------------------------------------------------- compile cache ----
def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    already reads it and nothing is overridden; otherwise the cache is
    ``.jax_cache/`` at the root of the checkout, resolved from this file's
    location so every run of one checkout finds the same directory (the
    path is part of the cache key; gitignored).  Entry points call this
    (``chip_smoke.py``, the ``launch.train``/``launch.serve`` CLIs,
    ``benchmarks.run``); importing the package never does, so test
    processes that compile for a described chip write no cache entries
    they could not read back."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path


# ------------------------------------------------ did-the-scheduler-fire ----
_ASYNC_MARKERS = ("all-gather-start", "collective-permute-start",
                  "all-reduce-start")


def async_collectives_in(hlo_text: str) -> bool:
    """Whether compiled HLO contains async collective start/done pairs —
    the measurable trace of the latency-hiding scheduler actually
    splitting a collective so it can overlap compute.  On single-stream
    CPU backends this is False even under the ``overlap`` preset; the
    bench records the answer rather than assuming the flags worked."""
    return any(m in hlo_text for m in _ASYNC_MARKERS)
