"""hvdlint — static analysis for collective consistency and concurrency
discipline, plus the cross-rank fingerprint verifier.

The reference Horovod's background runtime exists largely to catch one
failure class at runtime: ranks submitting collectives in different
orders or with mismatched shapes, which otherwise manifests as a silent
stall (controller.cc:74-447 mismatch checks, stall_inspector.cc). This
package moves that detection LEFT of the job launch:

* ``hvdlint`` (``python -m horovod_tpu.analysis``, the single
  ``make lint`` entrypoint) runs two AST rule families over Python
  source — collective-consistency rules (HVD0xx) on user/training code
  and the repo's examples, and concurrency-discipline rules (HVD1xx,
  including the ``# guarded-by:`` lock annotation convention) on the
  runtime itself — plus the HVD-ENV documentation-drift rule that
  subsumes the old ``scripts/check_env_docs.py``. The HVD0xx rules are
  interprocedural: ``callgraph`` builds a module-level call graph with
  transitive-collective and rank-taint summaries over every linted
  file, so helpers no longer hide divergence patterns. ``--format
  json`` and ``--baseline`` make CI gate on *new* findings only.

* ``race`` (**hvdrace**, ``HOROVOD_RACE_CHECK=1`` / ``make race``) is
  the runtime enforcement of ``# guarded-by:``: an Eraser-style
  lockset detector that instruments the annotated runtime classes at
  import time and reports any guarded attribute touched without its
  declared lock held — including stale annotations whose lock is never
  held at all.

* ``hlo`` / ``hlo_rules`` (**hvdhlo**, ``--hlo`` / ``--hlo-step`` /
  ``make hlo-lint``) lint the *lowered* XLA step program (HVD2xx:
  giant-allreduce plans, host round-trips, missing donation, lane
  padding, bf16 upcasts) — perf contracts invisible to an AST linter.

* ``shard`` / ``shard_rules`` (**hvdshard**, ``--shard`` /
  ``--hlo-step lm_sharded`` / ``make shard-lint``) are the
  sharding-aware layer over the same lowered forms (HVD3xx):
  replicated tables, partitioner-inserted resharding collectives, a
  donation-aware static per-device peak-HBM estimate gating
  compile-time OOM, unused mesh axes, and
  all-reduce-that-should-be-reduce-scatter — the static gate in front
  of the GSPMD backend (ROADMAP item 3).

* ``schedule`` / ``sched_rules`` (**hvdsched**, ``--sched`` /
  ``--hlo-step lm_sharded`` / ``make sched-lint``) reconstruct the
  per-device *collective schedule* from the same lowered forms —
  every collective with its replica groups (explicit, V2 iota,
  permute source-target pairs), channel id and payload bytes, in
  scheduled order — and verify cross-device matching (HVD4xx):
  group members reaching different collectives or positions (the
  static deadlock the runtime verifier only catches live), permute
  chains that are not unions of disjoint cycles (the 1F1B hazard),
  inconsistently-ordered overlapping subset collectives, flat
  cross-slice all-reduces where ICI/DCN staging is available, and
  predicted exposed comms from the analytic per-axis cost model, to
  read beside the measured ``comms_by_axis``.

* ``verifier`` is the runtime companion (``HOROVOD_CHECK_COLLECTIVES=1``):
  each rank hashes its rolling sequence of
  ``(op, name, shape, dtype, process_set)`` tuples at the dispatch choke
  point in ``ops/collectives.py`` and periodically cross-checks the
  fingerprint through the rendezvous KV, so a divergent rank raises an
  actionable mismatch error (rank, call index, both fingerprints)
  instead of tripping the stall watchdog blind.

See docs/static_analysis.md for the rule catalog and suppression syntax.

The analysis modules themselves import only the standard library, but
``python -m horovod_tpu.analysis`` necessarily executes the parent
package's ``__init__`` (which needs jax). Environments without the
runtime stack get the same rules dependency-free by stubbing the parent
package first — ``scripts/check_env_docs.py`` shows the pattern.
"""

from horovod_tpu.analysis.driver import (  # noqa: F401
    Finding, lint_paths, lint_source, main, run_cli,
)
