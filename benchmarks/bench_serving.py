"""Serving benchmark: continuous-batching throughput/latency sweep.

Drives ONE :class:`repro.serve.ServeRuntime` (gemma2-2b smoke arch)
through a closed-loop concurrency sweep — the load generator keeps
exactly ``c`` client streams outstanding per point — and records
throughput (tokens/s, requests/s) plus latency and time-to-first-token
percentiles per concurrent-client count into ``BENCH_serving.json``.

Sharing one runtime across the whole sweep is the point: the trace
counters span every arrival pattern the sweep produces, so the record's
``compile_once`` claim ("one jitted prefill/admit/decode trace total")
is measured, not asserted.  Two more tracked claims ride along:

* ``deadline_honored`` — no completed request finished past its
  deadline, and a probe batch submitted with an already-expired
  deadline is rejected/evicted without producing tokens;
* ``slot_reuse`` — at least one slot served multiple requests (the
  fixed table actually recycles).

CI (the ``serving`` leg) runs ``--smoke``, gates on the claims, and
uploads the artifact.

  PYTHONPATH=src python benchmarks/bench_serving.py [--smoke]
      [--concurrency 1,2,4,8] [--out BENCH_serving.json]
"""
from __future__ import annotations

import argparse
import json


def main() -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="fewer requests per sweep point for CI")
    ap.add_argument("--concurrency", default="1,2,4,8",
                    help="comma-separated concurrent-client counts")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    import jax

    from repro.configs.gemma2_2b import smoke
    from repro.serve import (ServeConfig, ServeRuntime, STATUS_DONE,
                             make_prompts, run_closed_loop)

    arch = smoke()
    counts = [int(c) for c in args.concurrency.split(",")]
    sc = ServeConfig(slots=max(counts), max_prompt_len=8,
                     max_new_tokens=8, prefill_batch=min(4, max(counts)),
                     deadline_s=600.0)
    rt = ServeRuntime(arch, sc, seed=0)
    per_point = 4 if args.smoke else 16

    rows = []
    for i, c in enumerate(counts):
        prompts = make_prompts(c * per_point, sc.max_prompt_len,
                               arch.vocab, seed=10 + i)
        row = run_closed_loop(rt, prompts, concurrency=c)
        row["latency_ms"] = {k: (None if v is None else round(v * 1e3, 3))
                             for k, v in row.pop("latency_s").items()}
        row["ttft_ms"] = {k: (None if v is None else round(v * 1e3, 3))
                          for k, v in row.pop("ttft_s").items()}
        row["throughput_tok_s"] = round(row["throughput_tok_s"], 2)
        row["throughput_req_s"] = round(row["throughput_req_s"], 2)
        row["elapsed_s"] = round(row["elapsed_s"], 4)
        rows.append(row)
        print(f"[c={c}] tok/s={row['throughput_tok_s']} "
              f"p50={row['latency_ms']['p50']}ms "
              f"p99={row['latency_ms']['p99']}ms "
              f"done={row['by_status'][STATUS_DONE]}/{row['n_requests']}")

    # deadline probes: an effectively-expired deadline must never yield
    # a completed request (queued ones are rejected before any compute)
    probe_rids = [rt.submit([1 + i], deadline_s=1e-9) for i in range(4)]
    rt.drain()
    probes_blocked = all(rt.results[r].status != STATUS_DONE
                         for r in probe_rids)
    done = [r for r in rt.results.values() if r.status == STATUS_DONE]
    stats = rt.stats()
    claims = {
        "compile_once": stats["traces"] == {"prefill": 1, "admit": 1,
                                            "decode": 1},
        "deadline_honored": (probes_blocked and bool(done)
                             and all(r.finished <= r.deadline
                                     for r in done)),
        "slot_reuse": stats["max_slot_reuse"] > 1,
    }
    result = {
        "backend": jax.default_backend(),
        "mode": "smoke" if args.smoke else "full",
        "arch": arch.name,
        "serve": sc.to_dict(),
        "requests_per_client": per_point,
        "sweep": rows,
        "traces": stats["traces"],
        "max_slot_reuse": stats["max_slot_reuse"],
        "evictions": stats["evictions"],
        "claims": claims,
    }
    print(f"claims={claims}")
    with open(args.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {args.out}")
    return result


if __name__ == "__main__":
    main()
