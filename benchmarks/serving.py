"""Serving-cluster study: routing policy x replica count + failover.

Sweeps the :class:`repro.serve.ReplicaPool` over replicas in {1, 2, 4}
x router policies {round_robin, load_aware, prefix_aware} on a
shared-prefix workload (3 prompt families over a 10-token common
prefix each, so prefix-aware routing has real locality to exploit and
round-robin necessarily scatters the families), and measures

  * **throughput + latency** — tokens/s, p50/p99 TTFT, p50/p99
    per-token decode latency, queue wait (from ServeMetrics),
  * **prefill work** — tokens actually prefilled vs tokens reused from
    a routed-to prefix (the paper's automatic-communication argument
    applied to serving: the router exploits placement the caller never
    sees),
  * **failover** — an instance stops heartbeating mid-decode on a
    2-replica x 3-instance pool; membership confirms it dead (planned
    shrink, KV migrates to survivors) and later rejoins it (planned
    grow).  Recovery latency and migration bytes come from the pool's
    event log; the caller never touches fail/rejoin.

Gates (SystemExit on failure):

  1. every sweep cell's token streams are bit-identical to the
     1-replica round-robin reference — routing policy, replica count,
     and scheduler order must be invisible in the values;
  2. prefix-aware prefill work < round-robin prefill work on the
     shared-prefix workload (with a measured reuse count > 0);
  3. the failover run's streams are bit-identical to its fault-free
     twin, the shrink moved > 0 bytes, and membership both killed AND
     rejoined the instance with zero caller recovery calls.

Quick mode (CI smoke) shrinks the sweep to replicas {1, 2} and gates
only; timings on CI are noise.

Run:  PYTHONPATH=src python -m benchmarks.serving [--quick]
      python -m benchmarks.run serve            # quick smoke (CI)

Full mode writes results/serving.json + BENCH_serve.json (quick mode
writes results/serving_quick.json only).
"""
from __future__ import annotations

import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

POLICIES = ["round_robin", "load_aware", "prefix_aware"]


def _model():
    import jax

    from repro.configs import get_config
    from repro.models import build

    cfg = get_config("yi-9b").reduced()
    bundle = build(cfg)
    params, _ = bundle.init(jax.random.PRNGKey(0))
    return bundle, params


def _workload(vocab: int, quick: bool) -> List[np.ndarray]:
    """3 shared-prefix families x 3 prompts (2 in quick mode): suffix
    lengths cycle {3, 4} to bound the number of prefill shapes."""
    rng = np.random.default_rng(0)
    families = [rng.integers(0, vocab, 10) for _ in range(3)]
    per = 2 if quick else 3
    return [np.concatenate([families[f], rng.integers(0, vocab, 3 + i % 2)])
            for i in range(per) for f in range(3)]


def _serve(bundle, params, scfg, prompts, replicas, policy,
           instances=2, max_new=6, fail=None, ticks: Optional[int] = None):
    """One pool run; returns (streams, metrics export, wall seconds).
    `fail` = (replica, rank, at_tick, down_for) suppresses heartbeats
    via the injection harness — recovery is membership's job."""
    from repro.serve import MembershipConfig, ReplicaPool

    pool = ReplicaPool(bundle, params, scfg, replicas=replicas,
                       instances=instances, policy=policy,
                       membership=MembershipConfig(suspect_after=1,
                                                   dead_after=2,
                                                   rejoin_after=2))
    rids = [pool.submit(p, max_new=max_new) for p in prompts]
    t0 = time.perf_counter()
    if ticks is None:
        pool.run(max_ticks=200)
    else:
        for tick in range(1, ticks + 1):
            if fail is not None and tick == fail[2]:
                pool.inject_instance_failure(fail[0], fail[1],
                                             down_for=fail[3])
            pool.step()
        if pool.pending:
            raise SystemExit(f"serving run left {pool.pending} requests "
                             f"pending after {ticks} ticks")
    wall = time.perf_counter() - t0
    streams = [pool.result(r) for r in rids]
    return streams, pool.export_metrics(), wall


def _gate(name: str, ok: bool) -> None:
    if not ok:
        raise SystemExit(f"SERVING GATE FAILURE: {name}")


def main(quick: bool = False) -> dict:
    from repro.launch.mesh import ensure_host_devices
    ensure_host_devices(8)

    from repro.serve import ServeConfig

    bundle, params = _model()
    # 2-token prefill chunks: the 10-token family prefixes are whole
    # chunks, so prefix reuse can copy all of them
    scfg = ServeConfig(max_seq=64, slots=2, prefix_reuse=True,
                       prefill_chunk=2)
    prompts = _workload(bundle.cfg.vocab, quick)
    replica_counts = [1, 2] if quick else [1, 2, 4]

    # -- the sweep ------------------------------------------------------
    rows: List[Dict] = []
    ref_streams = None
    work: Dict[str, int] = {}
    for replicas in replica_counts:
        for policy in POLICIES:
            streams, m, wall = _serve(bundle, params, scfg, prompts,
                                      replicas, policy)
            if ref_streams is None:
                ref_streams = streams
            _gate(f"streams replicas={replicas} policy={policy} "
                  "diverged from the 1-replica reference",
                  streams == ref_streams)
            prefill = sum(s["prefill_tokens_computed"]
                          for s in m["replicas"].values())
            reused = sum(s["prefix_tokens_reused"]
                         for s in m["replicas"].values())
            if replicas == replica_counts[-1]:
                work[policy] = prefill
            rows.append(dict(
                replicas=replicas, policy=policy, wall_s=wall,
                requests=m["counts"]["done"],
                tokens=m["tokens_generated"],
                throughput_tok_s=m["throughput_tok_s"],
                ttft_p50_s=m["ttft_s"]["p50"],
                ttft_p99_s=m["ttft_s"]["p99"],
                token_latency_p50_s=m["token_latency_s"]["p50"],
                token_latency_p99_s=m["token_latency_s"]["p99"],
                queue_wait_p50_s=m["queue_wait_s"]["p50"],
                prefill_tokens_computed=prefill,
                prefix_tokens_reused=reused))

    _gate("prefix-aware routing did not reuse any prefix tokens",
          any(r["policy"] == "prefix_aware" and r["prefix_tokens_reused"] > 0
              for r in rows))
    _gate(f"prefix-aware prefill work {work['prefix_aware']} not below "
          f"round-robin {work['round_robin']} on the shared-prefix "
          "workload", work["prefix_aware"] < work["round_robin"])

    # -- membership-driven failover ------------------------------------
    # instance (replica 0, rank 1) stops heartbeating at tick 3 and
    # resumes 6 ticks later: dead at tick 4 (shrink + replay), rejoined
    # at tick 9 (grow) — streams must match the fault-free twin.
    fo_kw = dict(replicas=2, instances=3, max_new=10, ticks=18)
    ref, _m, _w = _serve(bundle, params, scfg, prompts[:4],
                         policy="round_robin", **fo_kw)
    out, m, _w = _serve(bundle, params, scfg, prompts[:4],
                        policy="round_robin", fail=(0, 1, 3, 6), **fo_kw)
    _gate("failover run diverged from the fault-free twin", out == ref)
    fo = m["failover"]
    _gate("membership did not confirm the dead instance",
          fo["instance_losses"] == 1)
    _gate("membership did not rejoin the recovered instance",
          fo["instance_joins"] == 1)
    _gate("instance loss moved no bytes", fo["migration_bytes"] > 0)
    failover = dict(
        instance_losses=fo["instance_losses"],
        instance_joins=fo["instance_joins"],
        recovery_latency_s=fo["recovery_latency_s"][0],
        rejoin_latency_s=next(e["latency_s"] for e in m["events"]
                              if e["kind"] == "join"),
        migration_bytes=fo["migration_bytes"],
        streams_identical=True)

    # -- report ---------------------------------------------------------
    print(f"\n{'replicas':>8} {'policy':<13} {'tok/s':>8} "
          f"{'ttft_p50_ms':>11} {'ttft_p99_ms':>11} {'tok_p50_ms':>10} "
          f"{'prefillTok':>10} {'reusedTok':>9}")
    for r in rows:
        print(f"{r['replicas']:>8} {r['policy']:<13} "
              f"{r['throughput_tok_s']:>8.1f} "
              f"{r['ttft_p50_s'] * 1e3:>11.1f} "
              f"{r['ttft_p99_s'] * 1e3:>11.1f} "
              f"{r['token_latency_p50_s'] * 1e3:>10.1f} "
              f"{r['prefill_tokens_computed']:>10} "
              f"{r['prefix_tokens_reused']:>9}")
    print(f"# failover: recovery {failover['recovery_latency_s']*1e3:.1f}ms, "
          f"rejoin {failover['rejoin_latency_s']*1e3:.1f}ms, "
          f"{failover['migration_bytes']/1e3:.1f}KB migrated, "
          "streams bit-identical")

    out = {"quick": quick, "prompts": len(prompts),
           "prefix_work": work, "rows": rows, "failover": failover}
    os.makedirs("results", exist_ok=True)
    dest = "results/serving_quick.json" if quick else "results/serving.json"
    with open(dest, "w") as f:
        json.dump(out, f, indent=1)
    if not quick:
        with open("BENCH_serve.json", "w") as f:
            json.dump(out, f, indent=1)
    print(f"# -> {dest}" + ("" if quick else " + BENCH_serve.json"))
    print("# gates passed: streams bit-identical across every policy, "
          "replica count, and the membership-driven failover")
    return out


if __name__ == "__main__":
    main(quick="--quick" in sys.argv[1:])
