"""``repro.serve_engine`` — power-budget-aware multi-operating-point serving.

The paper's deployment claim is that PANN "seamlessly traverses the
power-accuracy trade-off at deployment time": moving along a Fig.-3
equal-power curve only changes ``(b~x, R)``, never the architecture. This
package turns that claim into a serving runtime:

  ladder      plan a small set of equal-power operating points (2/3/4/6-bit
              unsigned-MAC budgets by default) from ``planner.plan_ladder``
  scheduler   continuous-batching request scheduler that picks the rung per
              request from a declared power budget or accuracy floor
  engine      ``ServeEngine``: one bf16 checkpoint in, ONE max-budget
              weight store with a zero-copy view per rung
              (models/serving.build_weight_store), ONE jitted decode step
              shared by every rung, per-token bit-flip accounting in every
              response
  encoder     ``EncodeEngine``: the same ladder / weight store / no-retrace
              invariants for ITEM-oriented encoder workloads (vision conv
              stems, speech frontends) — whole-sequence waves, no KV cache,
              per-image/per-utterance power budgets (docs/encoder.md)
  artifact    the mmap-able on-disk form of the weight store
              (manifest.json + weights.bin; docs/artifact.md)
  fleet       ``Fleet``: ServeEngine replicated across simulated hosts on
              ``repro.dist`` — prefill/decode disaggregation, rung-sharded
              rung views served from ONE mmap artifact, and a
              telemetry-driven power governor holding the whole fleet
              under a global Gbit-flips/sec cap (docs/fleet.md)

Design notes live in DESIGN.md §6, §11 and §12; the end-to-end traversal
benchmark is ``benchmarks/serve_traversal.py`` and the fleet simulation is
``benchmarks/fleet_sim.py``.
"""
from repro.serve_engine.artifact import (ArtifactError, load_artifact,
                                         write_artifact)
from repro.serve_engine.encoder import (EncodeEngine, EncodeRequest,
                                        EncodeResponse)
from repro.serve_engine.engine import Lane, ServeEngine
from repro.serve_engine.fleet import (Fleet, FleetConfig, FleetTrace,
                                      PowerGovernor, TrafficSpec,
                                      make_trace, verify_streams)
from repro.serve_engine.ladder import OperatingPoint, build_ladder, select_rung
from repro.serve_engine.scheduler import Request, Response, Scheduler

__all__ = ["ServeEngine", "Lane", "OperatingPoint", "build_ladder",
           "select_rung", "Request", "Response", "Scheduler",
           "EncodeEngine", "EncodeRequest", "EncodeResponse",
           "ArtifactError", "load_artifact", "write_artifact",
           "Fleet", "FleetConfig", "FleetTrace", "PowerGovernor",
           "TrafficSpec", "make_trace", "verify_streams"]
