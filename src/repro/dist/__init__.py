"""``repro.dist`` — the distribution substrate.

Everything the model/launch layers need to run the same program on one CPU
device or a 512-chip ("pod", "data", "model") mesh:

  constrain    mesh-aware ``with_sharding_constraint`` wrappers that no-op
               cleanly when no mesh is active (single-device smoke tests)
  sharding     greedy PartitionSpec assignment for params / caches / inputs
  collectives  compressed (int8 + error feedback) gradient all-reduce
  pipeline     GPipe-style microbatch pipelining over a mesh axis
  moe_ep       expert-parallel capacity routing for MoE layers
  fault        straggler telemetry + checkpoint/restart supervision

Module layout and invariants are documented in DESIGN.md §3.
"""
