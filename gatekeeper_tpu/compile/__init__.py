"""Incremental compile plane: the compile cache and its content-addressed,
fingerprint-attested archive (docs/compile.md).

The store keeps an attested archive beside the persistent XLA compile
cache: every entry this process compiles is content-addressed (sha256)
and attested with the machine fingerprint that produced it (platform +
CPU feature set + jaxlib version + device kind). A foreign or corrupt
archived artifact is skipped and counted
(`program_store_rejected_total{reason}`), never copied into the cache.
The cache is where `compile_cache_dir()` says: JAX_COMPILATION_CACHE_DIR,
else `<checkout>/.cache/jax`. XLA reads every entry there, so one cache
directory may be shared only by identical hosts.
"""

from .store import (  # noqa: F401
    SCHEMA_VERSION,
    ProgramStore,
    compile_cache_dir,
    machine_fingerprint,
    store_from_env,
)
