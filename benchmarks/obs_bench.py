"""Observability-layer benchmark: structured round logs + trace export.

Drives the unified telemetry layer (DESIGN.md §12) end-to-end the way a
downstream consumer would: run ``train_fedgbf --log-json --trace`` as a
subprocess on a small local-backend config, parse the per-round JSON lines
back with ``repro.obs.log.parse_round_log`` (this module IS the consumer the
``--log-json`` satellite names), and validate the exported Chrome-trace
artifact loads and carries the expected event schema.

Reported:
  * ``rounds_parsed``     — structured lines recovered from mixed stdout
    (banners + JSON interleaved, exactly like a real log pipeline);
  * ``total_wall_s``      — sum of per-round ``wall_s`` from the log lines
    (the per-segment-true timings, not the old uniform smear);
  * ``log_line_bytes_mean`` — per-round log-line cost on the wire;
  * ``trace_events`` / ``trace_bytes`` — exported trace size and the
    schema checks (X events per round, thread_name tracks, counters);
  * ``faults_injected`` / ``fault_retries`` — a second federated run under
    a pinned faulty chaos spec + party dropout (DESIGN.md §13): every
    round line must carry the ``faults`` record (faults_injected /
    retries / degraded_parties) through ``parse_round_log``, and the
    Perfetto export must carry the ``faults`` track.

    PYTHONPATH=src python -m benchmarks.obs_bench
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile

from benchmarks.common import cpu_child_env, save_report, scale
from repro.obs import log as obs_log

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(smoke: bool = False) -> list:
    quick = smoke or scale() == "quick"
    rounds = 4 if quick else 12
    n = 2_000 if quick else 10_000

    trace_path = os.path.join(tempfile.mkdtemp(prefix="obs_bench_"),
                              "train_trace.json")
    cmd = [
        sys.executable, "-m", "repro.launch.train_fedgbf",
        "--dataset", "default_credit_card", "--n", str(n),
        "--rounds", str(rounds), "--eval-every", "2",
        "--log-json", "--trace", trace_path,
    ]
    env = cpu_child_env()
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    proc = subprocess.run(cmd, env=env, check=True, capture_output=True,
                          text=True, cwd=ROOT)

    # -- consume the structured log exactly as a pipeline would --------------
    recs = obs_log.parse_round_log(proc.stdout)
    assert len(recs) == rounds, (
        f"expected {rounds} round lines, parsed {len(recs)}:\n{proc.stdout}"
    )
    assert [r["round"] for r in recs] == list(range(1, rounds + 1))
    evaluated = [r for r in recs if r["metrics"] is not None]
    assert evaluated, "eval_every rounds must carry metrics in the log"
    json_lines = [l for l in proc.stdout.splitlines()
                  if l.startswith("{")]
    line_bytes = sum(len(l.encode()) for l in json_lines) / len(json_lines)

    # -- trace artifact schema ----------------------------------------------
    with open(trace_path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    xs = [e for e in events if e["ph"] == "X"]
    round_spans = [e for e in xs if e["name"].startswith("round ")]
    assert len(round_spans) == rounds, (
        f"trace must carry one round span per round "
        f"(got {len(round_spans)}/{rounds})"
    )
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in events)
    assert any(e["ph"] == "C" for e in events), "liveness counters missing"

    # -- fault telemetry (DESIGN.md §13): chaos + dropout run ----------------
    # Re-run federated under a seeded faulty chaos spec + party dropout and
    # assert the per-round fault fields survive the full pipeline: emitted
    # in the --log-json lines, recovered by parse_round_log, and exported
    # as the Perfetto "faults" track.  Seeds are pinned so the plan is
    # deterministic: chaos seed 1 injects >= 1 fault over the 3-slot tree,
    # dropout seed 0 degrades parties without ever losing a whole round.
    fault_trace = os.path.join(os.path.dirname(trace_path),
                               "fault_trace.json")
    fault_cmd = [
        sys.executable, "-m", "repro.launch.train_fedgbf",
        "--dataset", "default_credit_card", "--n", str(min(n, 2_000)),
        "--rounds", str(rounds), "--eval-every", "2",
        "--backend", "vfl-histogram", "--parties", "2",
        "--chaos-drop", "0.2", "--chaos-corrupt", "0.1", "--chaos-seed", "1",
        "--party-dropout", "0.6", "--dropout-seed", "0", "--retry-max", "1",
        "--log-json", "--trace", fault_trace,
    ]
    fault_env = dict(env)
    fault_env.setdefault("XLA_FLAGS",
                         "--xla_force_host_platform_device_count=8")
    fproc = subprocess.run(fault_cmd, env=fault_env, check=True,
                           capture_output=True, text=True, cwd=ROOT)
    frecs = obs_log.parse_round_log(fproc.stdout)
    assert len(frecs) == rounds, (
        f"chaos run: expected {rounds} round lines, parsed {len(frecs)}:\n"
        f"{fproc.stdout}"
    )
    assert all("faults" in r for r in frecs), (
        "every round line of a chaos run must carry the faults record"
    )
    assert all({"faults_injected", "retries", "degraded_parties"}
               <= set(r["faults"]) for r in frecs), (
        "fault records must carry faults_injected/retries/degraded_parties"
    )
    faults_injected = sum(r["faults"]["faults_injected"] for r in frecs)
    fault_retries = sum(r["faults"]["retries"] for r in frecs)
    assert faults_injected > 0, "pinned chaos seed must inject faults"
    assert fault_retries > 0, "injected faults must surface as retries"
    with open(fault_trace) as f:
        fdoc = json.load(f)
    fault_spans = [e for e in fdoc["traceEvents"]
                   if e["ph"] == "X" and e["name"].startswith("faults ")]
    assert fault_spans, "Perfetto export must carry the faults track"

    results = {
        "rounds": rounds, "n": n,
        "rounds_parsed": len(recs),
        "rounds_evaluated": len(evaluated),
        "total_wall_s": sum(r["wall_s"] for r in recs),
        "log_line_bytes_mean": line_bytes,
        "trace_events": len(events),
        "trace_bytes": os.path.getsize(trace_path),
        "liveness_in_log": all("liveness" in r for r in recs),
        "fault_rounds_parsed": len(frecs),
        "faults_injected": faults_injected,
        "fault_retries": fault_retries,
        "fault_trace_spans": len(fault_spans),
    }
    save_report("obs_bench", results)
    print(
        f"  {len(recs)} round lines parsed ({line_bytes:.0f} B/line, "
        f"{len(evaluated)} with metrics), total wall "
        f"{results['total_wall_s']*1e3:.1f} ms\n"
        f"  trace: {len(events)} events, "
        f"{results['trace_bytes']/1e3:.1f} kB -> ui.perfetto.dev\n"
        f"  faults: {faults_injected} injected / {fault_retries} retries "
        f"across {len(frecs)} chaos rounds, {len(fault_spans)} fault "
        f"spans in the trace"
    )
    return [
        ("obs/log_line", line_bytes,
         f"{len(recs)} structured rounds parsed back"),
        ("obs/trace_export", float(results["trace_bytes"]),
         f"{len(events)} events, schema-validated"),
    ]


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
