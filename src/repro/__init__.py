"""SP-FL: importance-aware wireless federated learning on jax/Pallas.

Every seeded quantity here (cell geometry, data partitions, channel and
cohort draws) and every operating point the tests and ``BENCH_*.json``
histories pin was drawn from jax's original threefry bit stream.  jax
0.5 changed the default of ``jax_threefry_partitionable`` to True, which
draws different bits for the same key; the package pins the original
stream so that a seed names the same run it always did.
"""
import jax

jax.config.update('jax_threefry_partitionable', False)
