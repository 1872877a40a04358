"""repro.dist — the training-side counterpart of the netsim fabric model.

SeqBalance's motivating traffic mode is AI training: a handful of huge,
synchronized grad-sync collectives that ECMP cannot spread and that must
not reorder.  This package supplies that side of the reproduction:

  * ``collectives`` — PathPlan + the chunked, multipath, bidirectional ring
    all-reduce (the Shaper's N-sub-flow idea applied to grad sync);
  * ``sharding``    — FSDP+TP parameter/batch/cache partition rules for the
    production 16x16 (and 2x16x16 multi-pod) meshes;
  * ``elastic``     — phi-window path quarantine (LinkHealth), pod-failure
    remesh planning and the straggler watchdog;
  * ``netfeed``     — one netsim co-simulation cycle: PathPlan -> ring-trace
    workload -> fluid sim -> per-path congestion -> LinkHealth -> new plan;
  * ``cosim``       — the multi-epoch driver over a mutable fault schedule
    (killed/recovering spines, brown-outs): phi-expiry releases quarantined
    paths, per-epoch FCT/imbalance/plan-churn land in a CosimHistory, and
    link capacity rides through the sweep as a traced operand so every
    epoch reuses one compiled program (the Fig. 11 convergence story).
"""
