"""Small dependency-free utilities shared across the stack.

:mod:`repro.utils.atomic` — the single crash-safe artifact writer
(tmp + fsync + rename, sha256-checksummed JSON manifests) used by both
``repro.index.store`` persistence and ``repro.training.checkpoint``.

:mod:`repro.utils.compile_cache` — where the entry points keep JAX's
persistent compilation cache.
"""
from repro.utils.atomic import (ArtifactCorruptionError, ArtifactError,
                                ArtifactVersionError, atomic_write_bytes,
                                atomic_write_json, atomic_write_text,
                                load_arrays, save_arrays, sha256_hex)
from repro.utils.compile_cache import compile_cache_dir, enable_compile_cache

__all__ = ["ArtifactError", "ArtifactCorruptionError",
           "ArtifactVersionError", "atomic_write_bytes",
           "atomic_write_json", "atomic_write_text", "load_arrays",
           "save_arrays", "sha256_hex", "compile_cache_dir",
           "enable_compile_cache"]
