"""graphite_tpu — a TPU-native distributed many-core timing simulator.

A ground-up JAX/XLA re-design of the capabilities of MIT's Graphite
(reference: /root/reference, HPCA 2010): it consumes per-tile
instruction/memory event streams and advances thousands of simulated
tiles — core pipeline models, private/shared L1/L2 cache hierarchies with
directory coherence, electrical-mesh/optical NoC models with contention
queueing, DRAM, DVFS, and power accounting — as vmapped per-tile state
machines stepped one lax-barrier quantum at a time.  The tile axis is
sharded over a `jax.sharding.Mesh` so ICI collectives replace the
reference's socket transport (reference: common/transport/socktransport.cc)
and MCP control plane (reference: common/system/mcp.cc).

Execution model (contrast with the reference):
  * Graphite runs one host thread per simulated tile, each advancing its
    tile event-by-event, with TCP sockets carrying modeled packets between
    host processes and a barrier server bounding clock skew
    (reference: common/system/clock_skew_management_schemes/).
  * graphite_tpu runs *all* tiles as one array program: simulation state is
    a pytree of arrays shaped [num_tiles, ...]; each jitted step advances
    every tile through one synchronization quantum; the lax-barrier is a
    reduction over the tile axis instead of a server thread.

Simulated time is int64 picoseconds throughout, matching the reference's
Time convention (reference: common/misc/time_types.h:7-60), so the package
enables jax_enable_x64 at import.
"""

import os as _os

import jax as _jax

_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache, one policy for every entry point:
# JAX_COMPILATION_CACHE_DIR, when set, is the cache (jax reads it itself
# and nothing here overrides it).  Otherwise a checkout (pyproject.toml
# beside the package) keeps it at the fixed path <checkout>/.jax_cache —
# the path is part of the cache key, so a moving directory never hits;
# an installed package keeps none.
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _root = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    if _os.path.exists(_os.path.join(_root, "pyproject.toml")):
        _jax.config.update("jax_compilation_cache_dir",
                           _os.path.join(_root, ".jax_cache"))

__version__ = "0.1.0"

from graphite_tpu.config import Config, ConfigError, load_config  # noqa: E402,F401
