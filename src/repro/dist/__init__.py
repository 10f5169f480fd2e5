"""repro.dist — the distribution layer.

Everything mesh-, collective-, and partitioning-related lives here:

* :mod:`repro.dist.sharding`    — PartitionSpec derivation for params,
  batches, and decode state (megatron-style tensor parallelism on the
  ``model`` axis, data parallelism on ``pod``/``data``);
* :mod:`repro.dist.collectives` — hierarchical (pod-local reduce-scatter →
  cross-pod all-reduce → all-gather) gradient all-reduce;
* :mod:`repro.dist.compress`    — int8 wire-format compressed gradient
  all-reduce + error-feedback compression;
* :mod:`repro.dist.ep`          — shard_map all-to-all expert-parallel MoE.

No module here touches device state at import time.
"""
