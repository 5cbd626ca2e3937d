"""L0 device runtime: platform selection, device discovery, dtype policy.

Replaces the reference's ``torch.device`` / ``.to(device)`` layer
(SURVEY.md §1 L0): here device placement is owned by XLA — params are
materialized directly into device memory (HBM on TPU) with an explicit
sharding, and the ``DEVICE`` env contract (BASELINE.json:5) maps onto
``JAX_PLATFORMS``.

``apply_device_env`` must run before the first device use in the
process: the backend initializes lazily and cannot be changed after.
"""

from __future__ import annotations

import dataclasses
import os


_OFF = ("", "0", "false", "no", "off")

#: The DEVICE=tpu default cache directory: one fixed path inside the
#: checkout (listed in .gitignore).  Fixed because the directory is
#: part of the cache key — a path that moves between runs never hits.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".xla_cache",
)


def resolve_cache_dir(device: str, cache_dir: str | None = None) -> str | None:
    """Where this process keeps its compile artifacts (the persistent
    XLA cache and, beside it, the Pallas tuning table), or None = off.

    1. ``JAX_COMPILATION_CACHE_DIR`` set: that directory.  JAX reads
       the variable itself, so the program sets no directory in code —
       whoever launches the process (an operator, a CI harness) places
       the cache from outside.
    2. else the ``COMPILE_CACHE_DIR`` knob (``cache_dir``; None = the
       raw env var for config-less callers): a path, or
       ``0``/``off``/empty = disabled.
    3. else ``CHECKOUT_CACHE_DIR`` for DEVICE=tpu, off for DEVICE=cpu
       (CPU compiles are fast and golden tests want cold compiles).
    """
    outside = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if outside:
        return outside
    knob = cache_dir if cache_dir is not None \
        else os.environ.get("COMPILE_CACHE_DIR")
    if knob is not None and knob.strip().lower() in _OFF:
        return None
    if knob:
        return knob
    return CHECKOUT_CACHE_DIR if device == "tpu" else None


def enable_compilation_cache(device: str,
                             cache_dir: str | None = None) -> str | None:
    """Persistent XLA compilation cache: restarts reuse compiled
    executables instead of re-paying warmup.  This is the bottom rung
    of the compile-cache hierarchy (docs/compilation.md): jit's
    per-wrapper cache and the process-level ExecutableCache
    (runtime/compile_cache.py) sit above it and cover in-process reuse;
    this disk cache is what carries compiles ACROSS processes.

    The directory comes from ``resolve_cache_dir``; returns it (None =
    disabled).  ``jax_compilation_cache_dir`` is only updated from code
    when ``JAX_COMPILATION_CACHE_DIR`` did not already place the cache.
    """
    resolved = resolve_cache_dir(device, cache_dir)
    if resolved is None:
        return None
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        from jax.experimental.compilation_cache import compilation_cache

        os.makedirs(resolved, exist_ok=True)
        # jax latches the no-dir decision at its FIRST compile; a
        # process that already compiled something (benchmark harnesses,
        # tests) would ignore the dir without this reset.
        compilation_cache.reset_cache()
        jax.config.update("jax_compilation_cache_dir", resolved)
    # Cache everything the warmup compiles, not just the slow ones: a
    # restart then pays no compile at all, small executables included.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # Debug metadata (operation names, so the ``jax.named_scope`` paths
    # a device trace groups operations by) is part of the key: left out
    # — JAX's default — an executable cached by an older commit with
    # the same arithmetic comes back with THAT commit's names, or none
    # (seen on the chip, PR 25: ``jit_insert`` without ``slot_insert``).
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return resolved


def apply_device_env(device: str, compile_cache_dir: str | None = None
                     ) -> None:
    """Select and VERIFY the platform for DEVICE=tpu|cpu.

    tpu: platform selection is the environment's (the PJRT TPU plugin
    registers itself), but the result is checked — with no chip visible
    JAX would quietly pick the CPU and the server would come up "ready"
    on the wrong device, so anything but a ``tpu`` backend raises.
    cpu: force the CPU backend.

    Also enables the persistent compilation cache (see
    ``enable_compilation_cache``; ``compile_cache_dir`` is the
    ServiceConfig knob, None = env-var fallback) and tells
    ``runtime/compile_cache`` which directory that is
    (``/status.compile.persistent_cache``).
    """
    from .compile_cache import note_persistent_cache

    note_persistent_cache(enable_compilation_cache(device, compile_cache_dir))
    import jax

    if device != "cpu":
        plat = jax.default_backend()
        if plat != "tpu":
            raise RuntimeError(
                f"DEVICE={device} requested but jax selected the {plat!r} "
                "backend (no TPU visible to this process); run on a "
                "machine with a chip, or ask for DEVICE=cpu explicitly"
            )
        return
    os.environ["JAX_PLATFORMS"] = "cpu"
    # jax reads JAX_PLATFORMS once, at import, and callers of
    # build_service (tests, cellbench) have usually imported it by
    # now — so set the config too.  The backend initializes lazily:
    # this works any time before the first device use; afterwards we
    # can only verify.
    jax.config.update("jax_platforms", "cpu")
    # XLA CPU's default conv/matmul precision is reduced; CPU serving
    # is a correctness path, so buy back real f32 math.
    jax.config.update("jax_default_matmul_precision", "highest")
    plat = jax.default_backend()
    if plat != "cpu":
        raise RuntimeError(
            f"DEVICE=cpu requested but jax already initialized on {plat!r}; "
            "set JAX_PLATFORMS=cpu before starting the process"
        )


def get_devices():
    """All accelerator devices visible to this process, in stable order."""
    import jax

    return jax.devices()


@dataclasses.dataclass(frozen=True)
class DtypePolicy:
    """Mixed-precision policy tuned for the TPU MXU.

    bf16 params + bf16 compute keeps matmuls/convs on the MXU fast path
    and halves HBM traffic; logits/softmax come back in f32 so
    postprocessing (argmax, label probabilities, sampling) is exact.
    """

    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"
    output_dtype: str = "float32"

    @property
    def param_jnp(self):
        import jax.numpy as jnp

        return jnp.dtype(self.param_dtype)

    @property
    def compute_jnp(self):
        import jax.numpy as jnp

        return jnp.dtype(self.compute_dtype)

    @property
    def output_jnp(self):
        import jax.numpy as jnp

        return jnp.dtype(self.output_dtype)


def default_policy(device: str = "tpu") -> DtypePolicy:
    """bf16 on TPU; f32 on CPU (CPU bf16 is slow and golden tests want
    bit-comparable f32 math)."""
    if device == "cpu":
        return DtypePolicy("float32", "float32", "float32")
    return DtypePolicy()
