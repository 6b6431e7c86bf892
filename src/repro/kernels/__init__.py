"""Pallas kernels for the engine's hot relational primitives.

The cuDF-operator analogue layer (paper §3): each kernel accelerates one
physical primitive of the query engine and ships with a pure-jnp oracle in
``ref.py`` that tests/test_kernels.py sweeps it against in interpret mode.

* ``hash_probe``        -- open-addressing join-table build + probe
                           (HashJoin's inner loop);
* ``segmented_agg``     -- one-hot MXU scatter-add (HashAggregation's
                           segmented reduction);
* ``block_prefix_sum``  -- two-level scan producing stream-compaction
                           addresses (``DeviceTable.compact``);
* ``radix_histogram``   -- per-partition row counts (the exchange's
                           metadata phase).

``ops`` carries the jit'd public wrappers plus the engine's backend switch
(``use_pallas`` / ``use_backend``, see ``core`` for how the driver selects
a backend per query); ``ref`` carries the semantic ground truths.
"""

from . import ops, ref
from .ops import (
    BACKENDS,
    block_prefix_sum,
    build_table,
    current_backend,
    default_backend,
    hash_probe,
    radix_histogram,
    segmented_sum,
    set_default_backend,
    use_backend,
    use_pallas,
)

__all__ = [
    "ops", "ref", "BACKENDS",
    "block_prefix_sum", "build_table", "hash_probe",
    "radix_histogram", "segmented_sum",
    "current_backend", "default_backend", "set_default_backend",
    "use_backend", "use_pallas",
]
