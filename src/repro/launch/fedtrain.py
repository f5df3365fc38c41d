"""Federated training launcher — the paper's end-to-end driver.

Simulates P clients over a (synthetic stand-in of a) paper dataset, runs
one analytic federation round through ``core/engine.FederationEngine``
(wire × transport × scenario), and prints the paper's four metrics:
accuracy, train time (slowest client + coordinator), summed CPU time,
and Wh (process-CPU metered) — plus the wire's upload bytes.

``PYTHONPATH=src python -m repro.launch.fedtrain --dataset higgs
--clients 1000 --partition pathological --wire gram --transport stream
--scenario "dropout=0.3,late_join=0.2"``

``--faults "crash@upload:p3,flaky=0.1" --quorum 0.9 --journal wal.npz``
runs the round through the fault subsystem (``core/faults.py``):
injected failures are detected, retried/quarantined and priced, the
round commits at a sample-weighted quorum, and hierarchical folds
journal per-tier aggregates so a killed coordinator resumes
bit-identically (exit code 3 signals an injected ``die=N`` kill).

``--timeline "events=leave@t2:p3,revise@t3:p0"`` switches to the
event-driven multi-round path (``FederationEngine.run_events`` over a
``FederationLedger``): one solve per tick, only changed clients
recompute. ``--ledger-ckpt PATH`` persists the ledger after the run —
and, when the file already exists, restores it first and continues the
timeline from the saved tick with bit-identical state.
"""
from __future__ import annotations

import argparse
import dataclasses
import os

import numpy as np

from repro.core import predict_labels
from repro.core.engine import FederationEngine, TRANSPORTS
from repro.core.faults import CoordinatorKilled
from repro.core.ledger import FederationLedger
from repro.core.scenario import Scenario, Timeline
from repro.data import partition, synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.privacy import PrivacyPolicy


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="higgs",
                    choices=sorted(synthetic.SPECS))
    ap.add_argument("--scale", type=float, default=2e-3,
                    help="dataset size scale (1.0 = paper size)")
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--partition", default="iid",
                    choices=sorted(partition.PARTITIONERS))
    ap.add_argument("--wire", default="svd", choices=["svd", "gram"])
    ap.add_argument("--transport", default="local",
                    choices=list(TRANSPORTS))
    ap.add_argument("--backend", default=None, choices=["xla", "pallas"],
                    help="gram-wire client pass (default: pallas on TPU, "
                         "xla elsewhere)")
    ap.add_argument("--scenario", default="none",
                    help='availability spec, e.g. '
                         '"dropout=0.3,late_join=0.2,straggler_frac=0.1,'
                         'straggler_delay=0.5" (see core/scenario.py)')
    ap.add_argument("--chunks", type=int, default=4,
                    help="chunks per client on the stream transport")
    ap.add_argument("--topology", default="none",
                    help='hierarchical aggregation spec, e.g. '
                         '"fanout=64,tiers=3,rtt=0.05,bw=1e6" — clients '
                         'fold through edge/regional tiers so no '
                         'aggregator ever holds more than fanout stats '
                         '(see core/topology.py); single-round only, '
                         'incompatible with --timeline')
    ap.add_argument("--batch-clients", action="store_true",
                    help="fleet-batched client phase: one dispatch per "
                         "power-of-two shape bucket (local transport)")
    ap.add_argument("--fused", action="store_true",
                    help="fuse client stats + merge (+ solve) into one "
                         "jitted program per bucket (implies "
                         "--batch-clients)")
    ap.add_argument("--timeline", default=None,
                    help='ledger event stream, e.g. "events=join@t1:p5,'
                         'leave@t3:p2,revise@t4:p7" — runs one round '
                         'per tick (see core/scenario.Timeline)')
    ap.add_argument("--ledger-ckpt", default=None,
                    help="ledger checkpoint path: restored (and "
                         "continued) if it exists, saved after the run")
    ap.add_argument("--full-reagg", action="store_true",
                    help="timeline runs re-aggregate every active "
                         "client each tick (the baseline delta rounds "
                         "are priced against)")
    ap.add_argument("--privacy", default="none",
                    choices=["none", "secagg", "dp", "secagg+dp"],
                    help="privacy policy (privacy/policy.py): secagg = "
                         "pairwise-masked uploads (gram wire, bit-exact "
                         "aggregate), dp = clip + one-shot Gaussian "
                         "output perturbation, secagg+dp = distributed "
                         "noise under the masks; composes with every "
                         "transport and with --fused (a uniform masked "
                         "fused round is one dispatch) — the only "
                         "refused combination is --wire svd with a "
                         "secagg mode (DESIGN.md §10)")
    ap.add_argument("--epsilon", type=float, default=float("inf"),
                    help="DP budget per released model (inf = clip "
                         "only, no noise)")
    ap.add_argument("--delta", type=float, default=1e-5,
                    help="DP delta (one-shot Gaussian mechanism)")
    ap.add_argument("--clip", type=float, default=1.0,
                    help="per-row L2 clip bound applied client-side "
                         "before statistics (dp modes)")
    ap.add_argument("--faults", default="none",
                    help='fault-injection plan, e.g. '
                         '"crash@upload:p3,corrupt@wire:p7,'
                         'aggfail@tier1:g0,timeout:p5,replay:p4,'
                         'flaky=0.1,seed=0" — deterministic crashes, '
                         'corrupted/replayed uploads, flaky links with '
                         'retry+backoff, and tier-aggregator failover '
                         '(see core/faults.py)')
    ap.add_argument("--quorum", type=float, default=1.0,
                    help="commit the round once this sample-weighted "
                         "fraction of on-time uploads has folded; "
                         "stragglers merge in revise-style after the "
                         "committed first solve (default 1.0 = wait "
                         "for everyone)")
    ap.add_argument("--journal", default=None,
                    help="round-journal (WAL) path for hierarchical "
                         "rounds: per-tier aggregates commit as exact "
                         "digit snapshots; a coordinator killed "
                         "mid-fold resumes from this file "
                         "bit-identically (requires --topology)")
    ap.add_argument("--select", default="none",
                    help='budgeted client selection (core/contribution'
                         '.py, DESIGN.md §13): "topk:K" keeps the K '
                         'highest exact-LOO-utility clients, '
                         '"budget:J" greedily admits clients under a '
                         'joule budget (suffix B = upload-byte '
                         'budget), "frontier" selects everyone and '
                         'reports the accuracy-per-joule frontier; '
                         'scores are computed coordinator-side against '
                         'a validation split carved from train')
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record the round's flight-recorder trace "
                         "(obs/trace.py) and write Perfetto/Chrome-"
                         "trace JSON here — load it at ui.perfetto.dev; "
                         "also prints a per-phase console summary")
    ap.add_argument("--metrics", default=None, metavar="OUT.prom",
                    help="write a Prometheus-style textfile of the "
                         "round's counters (dispatches, wire bytes, "
                         "joules by category, span histograms) — "
                         "node-exporter textfile-collector format")
    ap.add_argument("--lam", type=float, default=1e-3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    if args.timeline is not None and args.topology not in (None, "none", ""):
        raise SystemExit(
            "[fedtrain] --topology is incompatible with --timeline: the "
            "ledger's delta rounds re-solve from its registry, which is "
            "inherently resident at the coordinator — there is no tier "
            "tree to fold it through; drop one of the two")
    if args.timeline is not None and (
            args.faults not in (None, "none", "") or args.quorum < 1.0
            or args.journal):
        raise SystemExit(
            "[fedtrain] --faults/--quorum/--journal are incompatible "
            "with --timeline: the event-driven ledger path models "
            "churn as explicit timeline events; drop one of the two")
    if args.journal and args.topology in (None, "none", ""):
        raise SystemExit(
            "[fedtrain] --journal needs --topology: the write-ahead "
            "log commits per-tier aggregates of the hierarchical fold")

    scenario = Scenario.parse(args.scenario)
    # --partition/--seed/--select are the defaults; an explicit
    # scenario key wins
    if "partition" not in args.scenario:
        scenario = dataclasses.replace(scenario, partition=args.partition)
    if "seed" not in args.scenario:
        scenario = dataclasses.replace(scenario, seed=args.seed)
    if "select" not in args.scenario and \
            args.select not in (None, "none", ""):
        scenario = dataclasses.replace(scenario, select=args.select)
    if scenario.select and args.timeline is not None:
        raise SystemExit(
            "[fedtrain] --select is incompatible with --timeline: "
            "selection scores one-shot rounds; an event-driven "
            "ledger's registry can be scored directly with "
            "core.contribution.loo_scores")

    X, y = synthetic.generate(args.dataset, scale=args.scale,
                              seed=args.seed)
    (Xtr, ytr), (Xte, yte) = synthetic.train_test_split(X, y)
    select_eval = None
    if scenario.select:
        # carve the scoring split from TRAIN (never test: selection is
        # part of training, and scoring against test would leak it)
        (Xtr, ytr), (Xva, yva) = synthetic.train_test_split(
            Xtr, ytr, train_frac=0.8, seed=args.seed + 1)
        select_eval = (Xva, yva)
    P = min(args.clients, len(ytr) // 2)
    policy = PrivacyPolicy(mode=args.privacy, epsilon=args.epsilon,
                           delta=args.delta, clip=args.clip,
                           seed=args.seed)
    tracer = None
    if args.trace or args.metrics:
        from repro.obs import Tracer
        tracer = Tracer()
    engine = FederationEngine(wire=args.wire, transport=args.transport,
                              scenario=scenario, act="logistic",
                              lam=args.lam, backend=args.backend,
                              chunks=args.chunks, warmup=True,
                              batch_clients=args.batch_clients,
                              fused=args.fused, privacy=policy,
                              topology=args.topology,
                              faults=args.faults, quorum=args.quorum,
                              journal=args.journal,
                              select_eval=select_eval, trace=tracer)
    print(f"[fedtrain] {args.dataset} (scale {args.scale}): "
          f"{len(ytr)} train / {len(yte)} test, {P} clients "
          f"({scenario.partition}), wire={args.wire} "
          f"transport={args.transport} privacy={policy.mode}")

    if args.timeline is not None:
        run_timeline(args, engine, Xtr, ytr, Xte, yte, P)
        _export_trace(args, tracer, report=None)
        return

    try:
        report = engine.run_dataset(Xtr, ytr, P, n_classes=2)
    except CoordinatorKilled as e:
        # injected mid-fold death (faults die=N): the journal already
        # holds every committed tier aggregate — a rerun with the same
        # --journal resumes and finishes bit-identically; the partial
        # trace still exports (the recorder is pure observation)
        print(f"[fedtrain] {e}")
        _export_trace(args, tracer, report=None)
        raise SystemExit(3)
    roles = report.roles
    pred = predict_labels(report.W, Xte, act="logistic")
    acc = float((np.asarray(pred) == yte).mean())
    print(f"[fedtrain] roles: {len(roles.on_time)} on-time, "
          f"{len(roles.late)} late-join, {len(roles.dropped)} dropped "
          f"({report.n_samples} samples federated)")
    print(f"[fedtrain] single round — accuracy {acc:.4f}")
    print(f"[fedtrain] train time (slowest client + coordinator): "
          f"{report.train_time:.3f}s")
    print(f"[fedtrain] sum of CPU time: {report.cpu_time:.3f}s | "
          f"metered process CPU {report.cpu_seconds:.3f}s "
          f"({report.wh * 1000:.3f} mWh @65W)")
    print(f"[fedtrain] wire bytes uploaded ({args.wire}): "
          f"{report.wire_bytes / 1024:.1f} KiB | client-phase dispatches: "
          f"{report.dispatches}")
    _print_privacy(report)
    _print_hierarchy(report)
    _print_faults(report)
    _print_contribution(report)
    _export_trace(args, tracer, report)


def _export_trace(args, tracer, report):
    """Write --trace / --metrics artefacts and the console summary."""
    if tracer is None:
        return
    from repro.obs import (console_summary, write_perfetto,
                           write_prometheus)
    if args.trace:
        write_perfetto(tracer, args.trace)
        print(f"[fedtrain] trace → {args.trace} "
              f"({len(tracer.spans)} spans, {len(tracer.events)} "
              "events; load at ui.perfetto.dev)")
    if args.metrics:
        write_prometheus(tracer, args.metrics, report=report)
        print(f"[fedtrain] metrics → {args.metrics}")
    print(console_summary(tracer, report))


def _print_contribution(report):
    c = report.contribution
    if not c:
        return
    budget = ""
    if c["budget_j"] is not None:
        budget = f" budget {c['budget_j']:g}J"
    elif c["budget_bytes"] is not None:
        budget = f" budget {c['budget_bytes']}B"
    elif c["k"] is not None:
        budget = f" K={c['k']}"
    print(f"[fedtrain] selection ({c['mode']}{budget}): "
          f"{c['n_selected']}/{len(c['scores'])} clients kept — "
          f"spent {c['spent_bytes'] / 1024:.1f} KiB / "
          f"{c['spent_j']:.4f}J uplink, scored in {c['score_s']:.3f}s")
    top = sorted(c["scores"], key=lambda s: -s["d_acc"])[:3]
    print("[fedtrain] top contributors (exact LOO): " + ", ".join(
        f"p{s['cid']} Δacc {s['d_acc']:+.4f} @ {s['d_joules']:.5f}J"
        for s in top))
    if c["frontier"]:
        pts = c["frontier"]
        shown = pts if len(pts) <= 5 else \
            [pts[0], pts[len(pts) // 4], pts[len(pts) // 2],
             pts[3 * len(pts) // 4], pts[-1]]
        print("[fedtrain] accuracy-per-joule frontier: " + " | ".join(
            f"k={p['k']} acc {p['accuracy']:.4f} @ {p['cum_j']:.4f}J"
            for p in shown))


def _print_faults(report):
    f = report.faults
    quorum = f["quorum"]
    eventful = (f["quarantined"] or f["retried"] or f["failed_over"]
                or f["recovered"] or f["replays_rejected"]
                or quorum["target"] < 1.0)
    if not eventful:
        return
    line = f"[fedtrain] faults: {len(f['quarantined'])} quarantined"
    if f["quarantined"]:
        reasons = ", ".join(f"p{c}:{r}"
                            for c, r in sorted(f["quarantined"].items()))
        line += f" ({reasons})"
    line += (f", {sum(f['retried'].values())} retries "
             f"(+{f['retry_s']:.3f}s backoff, "
             f"{f['retry_bytes'] / 1024:.1f} KiB / "
             f"{f['retry_j']:.4f}J resent)")
    if f["replays_rejected"]:
        line += f", replays rejected {f['replays_rejected']}"
    print(line)
    if f["failed_over"] or f["recovered"]:
        print(f"[fedtrain] recovery: failed over "
              f"{f['failed_over'] or '[]'}, {f['recovered']} journal "
              "edge(s) recovered")
    if quorum["target"] < 1.0:
        print(f"[fedtrain] quorum: committed "
              f"{quorum['committed_frac']:.2f} of samples "
              f"({quorum['n_committed']} clients) at target "
              f"{quorum['target']:.2f}; {quorum['n_deferred']} "
              "deferred to the post-commit merge")


def _print_hierarchy(report):
    h = report.hierarchy
    if not h:
        return
    print(f"[fedtrain] topology: fanout={h['fanout']} tiers={h['tiers']} "
          f"mode={h['mode']} — {h['n_aggregators']} aggregators over "
          f"{h['n_participants']} clients")
    print(f"[fedtrain] coordinator peak "
          f"{report.peak_coordinator_bytes / 1024:.1f} KiB resident "
          f"(bound fanout·agg = {h['peak_bound_bytes'] / 1024:.1f} KiB)")
    print(f"[fedtrain] simulated round: tiered "
          f"{h['sim_wall_tiered']:.3f}s / {h['uplink_j_tiered']:.3f}J vs "
          f"flat {h['sim_wall_flat']:.3f}s / {h['uplink_j_flat']:.3f}J")


def _print_privacy(report):
    p = report.privacy
    if not p:
        return
    line = f"[fedtrain] privacy={p['mode']}"
    if p.get("upload_bytes"):
        line += (f" | masked upload {p['upload_bytes'] / 1024:.1f} KiB"
                 f"/client ({p['mod_bits']}-bit ring)")
    if p["releases"]:
        sig = p["sigma"] if p["sigma"] is not None else 0.0
        line += (f" | spent (ε={p['eps_spent']:g}, "
                 f"δ={p['delta_spent']:g}) over {p['releases']} "
                 f"release(s), σ={sig:.4g} (clip {p['clip']:g})")
    print(line)


def run_timeline(args, engine, Xtr, ytr, Xte, yte, P):
    """Event-driven rounds: ledger restore → run_events → save."""
    from repro.core import activations as acts
    timeline = Timeline.parse(args.timeline)
    ledger = None
    if engine.privacy.active:
        if args.ledger_ckpt:
            # secagg: masked ring elements don't checkpoint at all.
            # dp: a restored registry's statistics may predate the
            # clip bound σ was calibrated against — releasing over
            # them would silently void the (ε, δ) claim.
            raise SystemExit(
                "[fedtrain] --ledger-ckpt is incompatible with "
                "--privacy: masked ledgers do not checkpoint, and a "
                "restored registry cannot prove its statistics were "
                "clipped at this run's --clip (the sensitivity bound "
                "behind sigma); drop one of the two")
        # the engine mints the (masked) ledger itself when needed
    elif args.ledger_ckpt and os.path.exists(args.ledger_ckpt):
        ledger = FederationLedger.restore(args.ledger_ckpt,
                                          backend=args.backend or "xla")
        if ledger.wire.name != args.wire:
            raise SystemExit(
                f"[fedtrain] ledger checkpoint {args.ledger_ckpt} was "
                f"saved on the {ledger.wire.name!r} wire but --wire is "
                f"{args.wire!r}; rerun with --wire {ledger.wire.name}")
        if ledger.lam != args.lam:
            print(f"[fedtrain] note: checkpoint was saved with lam="
                  f"{ledger.lam:g}; continuing with --lam {args.lam:g}")
        print(f"[fedtrain] restored ledger from {args.ledger_ckpt}: "
              f"{len(ledger.clients)} clients, tick {ledger.tick}")
    if ledger is None and not engine.privacy.secagg:
        ledger = FederationLedger(engine.wire, lam=engine.lam)
    parts = engine.scenario.make_parts(Xtr, ytr, P)
    pX = [p[0] for p in parts]
    pD = [np.asarray(acts.encode_labels(p[1], 2)) for p in parts]
    reports = engine.run_events(pX, pD, timeline, ledger=ledger,
                                delta=not args.full_reagg)
    for r in reports:
        pred = predict_labels(r.W, Xte, act="logistic")
        acc = float((np.asarray(pred) == yte).mean())
        print(f"[fedtrain] tick {r.tick}: {len(r.roles.on_time)} active, "
              f"changed {list(r.changed) or '[]'} — acc {acc:.4f}, "
              f"train {r.train_time:.3f}s, ΣCPU {r.cpu_time:.3f}s, "
              f"{r.wire_bytes / 1024:.1f} KiB up, "
              f"{r.dispatches} dispatches")
    if not reports:
        print("[fedtrain] timeline: no ticks beyond the restored state")
    else:
        _print_privacy(reports[-1])
    total_cpu = sum(r.cpu_time for r in reports)
    total_wh = sum(r.wh for r in reports)
    mode = "full re-agg" if args.full_reagg else "delta"
    print(f"[fedtrain] {len(reports)} {mode} rounds — "
          f"ΣCPU {total_cpu:.3f}s, {total_wh * 1000:.3f} mWh, "
          f"Σ upload {sum(r.wire_bytes for r in reports) / 1024:.1f} KiB")
    if args.ledger_ckpt:
        ledger.save(args.ledger_ckpt)
        print(f"[fedtrain] saved ledger → {args.ledger_ckpt} "
              f"(tick {ledger.tick})")


if __name__ == "__main__":
    main()
