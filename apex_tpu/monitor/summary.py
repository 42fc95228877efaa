"""Render a monitor JSONL run log into a human summary.

The read side of the telemetry spine: :func:`load_events` parses an
append-only event log back into :class:`~apex_tpu.monitor.events.Event`
records (tolerating the one truncated trailing line a kill mid-write can
leave), :func:`summarize` folds them into a run-health digest —
throughput, loss trajectory, amp overflow history, watchdog alarms,
resilience lifecycle (preempts / resumes / restart attempts /
checkpoint-integrity skips), phase-timer totals, wall-time attribution
(the :mod:`~apex_tpu.monitor.tracing` waterfall: mean/p50/p99 per
component + worst-step pointer), the captured-traces index, the
serving digest (request lifecycle outcomes, queue-wait/TTFT/ITL
percentiles, rejection reasons, pool high-water, per-bucket tick
counts, engine snapshots) — and :func:`render` prints it as tables.
``tools/monitor_summary.py`` is the CLI wrapper (``--chrome OUT.json``
additionally rebuilds a Perfetto-loadable Chrome trace from the log's
span/timer events).
"""
from __future__ import annotations

import statistics
import sys
from typing import Dict, List, Optional

from .events import Event, terminal_reason


def load_events(path: str) -> tuple:
    """Parse a JSONL event log.  Returns ``(events, malformed)`` where
    ``malformed`` counts undecodable lines (a crash-truncated tail is
    expected and must not sink the post-mortem — the whole point of the
    line-per-event format)."""
    events: List[Event] = []
    malformed = 0
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(Event.from_json(line))
            except (ValueError, KeyError, TypeError):
                malformed += 1  # torn/garbled line: count, keep parsing
    return events, malformed


def _series(events: List[Event], kind: str, name: str) -> List[float]:
    return [float(e.value) for e in events
            if e.kind == kind and e.name == name
            and isinstance(e.value, (int, float))]


def _pct(vals: List[float], q: float) -> float:
    """Percentile by linear interpolation between closest ranks —
    stable for the handfuls of steps a smoke run produces (p99 of 3
    samples is the max, not an IndexError)."""
    s = sorted(vals)
    if len(s) == 1:
        return s[0]
    pos = (len(s) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(s) - 1)
    return s[lo] + (s[hi] - s[lo]) * (pos - lo)


def summarize(events: List[Event], malformed: int = 0) -> dict:
    """Fold an event stream into the run-health digest dict."""
    out: Dict[str, object] = {"n_events": len(events),
                              "malformed_lines": malformed}
    for e in events:
        if e.kind == "run" and e.name == "run_start":
            out["run"] = dict(e.attrs)
            break
    for e in reversed(events):
        if e.kind == "run" and e.name == "run_end":
            out["run_end"] = dict(e.attrs)
            break

    # step metrics --------------------------------------------------------
    losses = _series(events, "metric", "loss")
    step_ms = _series(events, "metric", "step_ms")
    tps = _series(events, "metric", "tokens_per_sec")
    mfu = _series(events, "metric", "mfu")
    steps = sorted({e.step for e in events
                    if e.kind == "metric" and e.step is not None})
    stats: Dict[str, object] = {"count": len(steps)}
    if steps:
        stats["first"], stats["last"] = steps[0], steps[-1]
    if losses:
        stats["loss_first"] = losses[0]
        stats["loss_last"] = losses[-1]
        stats["loss_min"] = min(losses)
    nonfinite = [e for e in events if e.kind == "metric"
                 and e.name == "loss" and "nonfinite" in e.attrs]
    if nonfinite:
        stats["nonfinite_losses"] = len(nonfinite)
    if step_ms:
        stats["step_ms_mean"] = statistics.fmean(step_ms)
        stats["step_ms_min"] = min(step_ms)
    if tps:
        stats["tokens_per_sec_mean"] = statistics.fmean(tps)
    if mfu:
        stats["mfu_mean"] = statistics.fmean(mfu)
    out["steps"] = stats

    # amp scale -----------------------------------------------------------
    scales = _series(events, "scale", "loss_scale")
    if scales:
        skipped = [e.attrs.get("steps_skipped") for e in events
                   if e.kind == "scale" and e.name == "loss_scale"]
        overflow_events = [e for e in events
                           if e.kind == "scale" and e.name == "overflow"]
        out["scale"] = {
            "first": scales[0], "last": scales[-1],
            "min": min(scales), "max": max(scales),
            "overflow_steps": len(overflow_events),
            "steps_skipped_total": next(
                (s for s in reversed(skipped) if s is not None), 0),
        }

    # alarms --------------------------------------------------------------
    alarms = [e for e in events if e.kind == "alarm"]
    if alarms:
        out["alarms"] = [
            {"name": e.name, "step": e.step, "value": e.value,
             **dict(e.attrs)} for e in alarms]

    # phase timers --------------------------------------------------------
    timers: Dict[str, Dict[str, float]] = {}
    for e in events:
        if e.kind != "timer" or not isinstance(e.value, (int, float)):
            continue
        t = timers.setdefault(e.name, {"count": 0, "total_s": 0.0})
        t["count"] += 1
        t["total_s"] += float(e.value)
    if timers:
        for t in timers.values():
            t["mean_ms"] = t["total_s"] * 1e3 / t["count"]
        out["timers"] = timers

    # wall-time attribution (tracing waterfall) ---------------------------
    wf_rows = [e for e in events
               if e.kind == "attr" and e.name == "step_waterfall"
               and isinstance(e.value, (int, float))]
    if wf_rows:
        comps: Dict[str, List[float]] = {"wall": []}
        ratios: List[float] = []
        worst = None
        for e in wf_rows:
            comps["wall"].append(float(e.value))
            for k, v in e.attrs.items():
                if k.endswith("_ms") and isinstance(v, (int, float)):
                    comps.setdefault(k[:-3], []).append(float(v))
            r = e.attrs.get("wall_device_ratio")
            if isinstance(r, (int, float)):
                ratios.append(float(r))
            if worst is None or float(e.value) > worst[1]:
                worst = (e.step, float(e.value), dict(e.attrs))
        wall_total = sum(comps["wall"]) or 1.0
        att: Dict[str, object] = {"steps": len(wf_rows), "components": {}}
        for name, vals in comps.items():
            att["components"][name] = {
                "mean_ms": statistics.fmean(vals),
                "p50_ms": _pct(vals, 50.0),
                "p99_ms": _pct(vals, 99.0),
                "share": sum(vals) / wall_total,
            }
        if ratios:
            att["wall_device_ratio_mean"] = statistics.fmean(ratios)
            att["wall_device_ratio_min"] = min(ratios)
        if worst is not None:
            att["worst_step"] = {"step": worst[0],
                                 "wall_ms": worst[1], **worst[2]}
        out["attribution"] = att

    # captured traces ------------------------------------------------------
    caps = [e for e in events if e.kind == "trace"]
    if caps:
        index: List[Dict[str, object]] = []
        for e in caps:
            if e.name == "capture_started":
                index.append({"step": e.step,
                              "reason": e.attrs.get("reason"),
                              "trace_dir": e.attrs.get("trace_dir"),
                              "stop": e.attrs.get("stop")})
            elif e.name == "capture_stopped" and index \
                    and "stopped_at" not in index[-1]:
                index[-1]["stopped_at"] = e.step
        requested = sum(1 for e in caps
                        if e.name == "capture_requested")
        out["captures"] = {"windows": index, "requested": requested}

    # resilience lifecycle ------------------------------------------------
    res = [e for e in events if e.kind == "resilience"]
    if res:
        counts: Dict[str, int] = {}
        for e in res:
            counts[e.name] = counts.get(e.name, 0) + 1
        digest: Dict[str, object] = {"counts": counts}
        resumed = [e for e in res if e.name == "run_resumed"]
        if resumed:
            digest["resumed_from"] = [int(e.value) for e in resumed
                                      if e.value is not None]
        preempt = [e for e in res if e.name == "preempt_exit"]
        if preempt:
            digest["preempted_at"] = [int(e.value) for e in preempt
                                      if e.value is not None]
        skipped = [e for e in res if e.name == "ckpt_skipped"]
        if skipped:
            digest["ckpt_skipped"] = [
                {"step": e.step, "reason": e.attrs.get("reason", "")}
                for e in skipped]
        giveup = [e for e in res if e.name == "run_giveup"]
        if giveup:
            digest["gave_up"] = dict(giveup[-1].attrs)
        out["resilience"] = digest

    # serving (request lifecycle + engine gauges) -------------------------
    srv = [e for e in events if e.kind == "serving"]
    ticks = [e for e in events if e.kind == "serve_tick"]
    fleet = [e for e in events if e.kind == "fleet"]
    # a supervisor-only log (ISSUE-18: kind='fleet' lifecycle events,
    # no request traffic of its own) still gets the serving section —
    # the control-plane ledger below must not require child logs in
    # the merge
    if srv or ticks or fleet:
        digest: Dict[str, object] = {}
        done_events = [e for e in srv if e.name == "request_done"]
        digest["submitted"] = sum(1 for e in srv
                                  if e.name == "request_submitted")

        def _terminal(e):
            return terminal_reason(e.attrs)

        digest["done"] = sum(1 for e in done_events
                             if _terminal(e) == "finished")
        digest["preempted"] = sum(1 for e in done_events
                                  if _terminal(e) == "preempted")
        # fleet runs (ISSUE-14): replica-stamped events aggregate to
        # one per-replica reconciliation table — N submitted must
        # equal N terminal per replica AND fleet-wide
        replicas: Dict[str, Dict[str, int]] = {}
        for e in srv:
            rep = e.attrs.get("replica")
            if rep is None or e.name not in ("request_submitted",
                                             "request_done"):
                continue
            row = replicas.setdefault(str(rep),
                                      {"submitted": 0, "terminal": 0})
            row["submitted" if e.name == "request_submitted"
                else "terminal"] += 1
        if replicas:
            digest["replicas"] = {k: replicas[k]
                                  for k in sorted(replicas)}
        if fleet:
            digest["fleet"] = {
                "routed": sum(1 for e in fleet
                              if e.name == "request_routed"),
                "kv_handoffs": sum(1 for e in fleet
                                   if e.name == "kv_handoff"),
                "swaps": sum(1 for e in fleet
                             if e.name == "swap_done"),
                "replica_restarts": sum(1 for e in fleet
                                        if e.name ==
                                        "replica_restart"),
            }
        # ISSUE-18 distributed control plane: the supervisor's
        # process-lifecycle ledger (spawn/reap pairing, restarts with
        # reasons, degraded RPCs, torn-handoff fallbacks, QoS
        # admission sheds) and the autoscale event trace — every
        # scaling decision with its round, direction, trigger and
        # resulting fleet size, in order
        spawned = [e for e in fleet if e.name == "replica_spawned"]
        if spawned:
            cp: Dict[str, object] = {
                "spawned": len(spawned),
                "reaped": sum(1 for e in fleet
                              if e.name == "replica_reaped"),
                "replayed_requests": sum(
                    int(e.attrs.get("replayed") or 0)
                    for e in spawned),
            }
            restarts = [e for e in fleet
                        if e.name == "replica_restart"]
            if restarts:
                cp["restarts"] = [
                    {"round": e.step,
                     "replica": e.attrs.get("replica"),
                     "reason": e.attrs.get("reason"),
                     "backoff_s": e.attrs.get("backoff_s")}
                    for e in restarts]
            rpc_to = sum(1 for e in fleet if e.name == "rpc_timeout")
            if rpc_to:
                cp["rpc_timeouts"] = rpc_to
            retries = sum(1 for e in fleet
                          if e.name == "kv_handoff_retry")
            if retries:
                cp["handoff_cold_fallbacks"] = retries
            sheds = [e for e in fleet
                     if e.name == "request_shed_admission"]
            if sheds:
                by_cls: Dict[str, int] = {}
                for e in sheds:
                    k = (f"{e.attrs.get('priority_class')}/"
                         f"{e.attrs.get('reason')}")
                    by_cls[k] = by_cls.get(k, 0) + 1
                cp["shed_admission"] = by_cls
            scale = [e for e in fleet if e.name == "autoscale"]
            if scale:
                cp["autoscale"] = [
                    {"round": e.step,
                     "action": e.attrs.get("action"),
                     "reason": e.attrs.get("reason"),
                     "replica": e.attrs.get("replica"),
                     "backlog": e.attrs.get("backlog"),
                     "replicas": e.attrs.get("replicas")}
                    for e in scale]
            digest["control_plane"] = cp
        # ISSUE-13 terminal paths: deadline expiry (queued OR
        # running) and load shedding — rendered so N submitted still
        # visibly reconciles against N terminal
        deadline = sum(1 for e in done_events
                       if _terminal(e).startswith("deadline"))
        shed = sum(1 for e in done_events if _terminal(e) == "shed")
        if deadline:
            digest["deadline_exceeded"] = deadline
        if shed:
            digest["shed"] = shed
        replays = [e for e in srv if e.name == "journal_replay"]
        if replays:
            digest["journal_replays"] = [
                {"tick": e.step,
                 "replayed": e.attrs.get("replayed"),
                 "skipped_terminal": e.attrs.get("skipped_terminal")}
                for e in replays]
        replayed = sum(1 for e in srv if e.name == "request_replayed")
        if replayed:
            digest["replayed_requests"] = replayed
        rejected: Dict[str, int] = {}
        for e in srv:
            if e.name == "request_rejected":
                r = str(e.attrs.get("reason", "unknown"))
                rejected[r] = rejected.get(r, 0) + 1
        if rejected:
            digest["rejected"] = rejected
        # distributions over the completed requests' terminal events
        # (queue wait / TTFT) and the decode ticks.  ITL is the tick
        # wall weighted by the tick's batch — every active request
        # gains one token per tick, so this is the same population as
        # the per-request samples ServeSummary.itl_p99_ms draws from
        itl: List[float] = []
        for e in srv:
            if e.name == "decode_step" \
                    and isinstance(e.value, (int, float)):
                n = e.attrs.get("batch")
                itl.extend([float(e.value)]
                           * (n if isinstance(n, int) and n > 0
                              else 1))
        series = {
            "queue_wait_ms": [e.attrs["queue_wait_ms"]
                              for e in done_events
                              if isinstance(e.attrs.get(
                                  "queue_wait_ms"), (int, float))],
            "ttft_ms": [e.attrs["ttft_ms"] for e in done_events
                        if isinstance(e.attrs.get("ttft_ms"),
                                      (int, float))],
            "itl_ms": itl,
        }
        dists: Dict[str, object] = {}
        for name, vals in series.items():
            if vals:
                dists[name] = {"mean": statistics.fmean(vals),
                               "p50": _pct(vals, 50.0),
                               "p90": _pct(vals, 90.0),
                               "p99": _pct(vals, 99.0),
                               "n": len(vals)}
        if dists:
            digest["latency"] = dists
        # per-bucket tick counts (the compiled-program ladder in use)
        buckets: Dict[str, int] = {}
        for e in srv:
            if e.name != "decode_step":
                continue
            bb, pb = e.attrs.get("batch_bucket"), \
                e.attrs.get("pages_bucket")
            if bb is not None and pb is not None:
                key = f"b{bb}xp{pb}"
                buckets[key] = buckets.get(key, 0) + 1
        if buckets:
            digest["bucket_ticks"] = buckets
        # pool-utilization high-water mark from the engine gauges
        hw = [e.attrs.get("used_blocks_high_water") for e in ticks]
        hw = [v for v in hw if isinstance(v, (int, float))]
        pool = [e.attrs.get("pool_blocks") for e in ticks]
        pool = [v for v in pool if isinstance(v, (int, float))]
        if hw:
            digest["pool_high_water_blocks"] = int(max(hw))
            if pool and max(pool) > 0:
                digest["pool_high_water_share"] = \
                    max(hw) / max(pool)
        if ticks:
            digest["gauge_events"] = len(ticks)
        snaps = [e for e in srv if e.name == "engine_snapshot"]
        if snaps:
            digest["snapshots"] = [
                {"tick": e.step, "reason": e.attrs.get("reason"),
                 "active": e.attrs.get("active"),
                 "queued": e.attrs.get("queued")} for e in snaps]
        # ISSUE-17 live metrics plane: SLO burn-rate digest (the
        # slo_burn alarms already render in the alarm table; this
        # reconciles them against the objective definitions and the
        # recovery records), fleet aggregation rounds, and the
        # exporter lifecycle pair
        slo_events = [e for e in events if e.kind == "slo"]
        burns = [e for e in events
                 if e.kind == "alarm" and e.name == "slo_burn"]
        if slo_events or burns:
            slo: Dict[str, object] = {}
            defs = [e for e in slo_events
                    if e.name == "slo_objectives"]
            if defs:
                slo["objectives"] = dict(defs[-1].attrs)
            slo["burn_episodes"] = len(burns)
            slo["recoveries"] = sum(1 for e in slo_events
                                    if e.name == "slo_recovered")
            if burns:
                slo["burns"] = [
                    {"tick": e.step,
                     "class": e.attrs.get("priority_class"),
                     "dimension": e.attrs.get("dimension"),
                     "burn_fast": e.attrs.get("burn_fast"),
                     "burn_slow": e.attrs.get("burn_slow")}
                    for e in burns]
            digest["slo"] = slo
        fticks = [e for e in events if e.kind == "fleet_tick"]
        if fticks:
            digest["fleet_ticks"] = len(fticks)
        mev = [e for e in events if e.kind == "metrics"]
        if mev:
            digest["metrics_server"] = {
                "started": sum(1 for e in mev
                               if e.name ==
                               "metrics_server_started"),
                "stopped": sum(1 for e in mev
                               if e.name ==
                               "metrics_server_stopped"),
            }
        out["serving"] = digest
    return out


def _fmt(v, nd=3) -> str:
    if isinstance(v, float):
        return f"{v:.{nd}f}" if abs(v) < 1e5 else f"{v:.3e}"
    return str(v)


def render(summary: dict) -> str:
    """Text tables for a terminal / CI log."""
    lines: List[str] = []
    run = summary.get("run", {})
    head = " ".join(f"{k}={v}" for k, v in run.items() if k != "schema")
    lines.append(f"run: {head or '(no run_start event)'}")
    if summary.get("malformed_lines"):
        lines.append(f"  ({summary['malformed_lines']} malformed line(s) "
                     "skipped — truncated tail from a killed run?)")

    st = summary.get("steps", {})
    if st.get("count"):
        lines.append("")
        lines.append(f"steps: {st['count']} "
                     f"({st.get('first')}..{st.get('last')})")
        row = []
        if "loss_first" in st:
            row.append(f"loss {_fmt(st['loss_first'], 4)} -> "
                       f"{_fmt(st['loss_last'], 4)} "
                       f"(min {_fmt(st['loss_min'], 4)})")
        if "nonfinite_losses" in st:
            row.append(f"NONFINITE x{st['nonfinite_losses']}")
        if "step_ms_mean" in st:
            row.append(f"step {_fmt(st['step_ms_mean'], 1)} ms mean "
                       f"/ {_fmt(st['step_ms_min'], 1)} ms best")
        if "tokens_per_sec_mean" in st:
            row.append(f"{_fmt(st['tokens_per_sec_mean'], 0)} tok/s")
        if "mfu_mean" in st:
            row.append(f"MFU {100.0 * st['mfu_mean']:.2f}%")
        for r in row:
            lines.append(f"  {r}")

    sc = summary.get("scale")
    if sc:
        lines.append("")
        lines.append(f"amp scale: {_fmt(sc['first'], 1)} -> "
                     f"{_fmt(sc['last'], 1)} "
                     f"[{_fmt(sc['min'], 1)}, {_fmt(sc['max'], 1)}], "
                     f"overflow steps {sc['overflow_steps']}, "
                     f"total skipped {sc['steps_skipped_total']}")

    alarms = summary.get("alarms")
    lines.append("")
    if alarms:
        lines.append(f"ALARMS ({len(alarms)}):")
        for a in alarms:
            extra = {k: v for k, v in a.items()
                     if k not in ("name", "step", "value")}
            lines.append(f"  {a['name']} @ step {a.get('step')} "
                         f"value={a.get('value')} {extra or ''}".rstrip())
    else:
        lines.append("alarms: none")

    res = summary.get("resilience")
    if res:
        lines.append("")
        counts = res.get("counts", {})
        lines.append("resilience: "
                     + " ".join(f"{k}={v}"
                                for k, v in sorted(counts.items())))
        if res.get("preempted_at"):
            lines.append(f"  preempted at step(s) {res['preempted_at']} "
                         "(clean exit)")
        if res.get("resumed_from"):
            lines.append(f"  resumed from step(s) {res['resumed_from']}")
        for s in res.get("ckpt_skipped", []):
            lines.append(f"  CKPT SKIPPED step {s['step']}: "
                         f"{s['reason']}")
        if res.get("gave_up"):
            lines.append(f"  GAVE UP: {res['gave_up']}")

    att = summary.get("attribution")
    if att:
        lines.append("")
        lines.append(f"wall-time attribution ({att['steps']} step(s)):")
        lines.append(f"{'component':<18} {'mean ms':>9} {'p50 ms':>9} "
                     f"{'p99 ms':>9} {'share':>7}")
        comps = att["components"]
        order = ["wall", "data_load", "dispatch", "device_compute",
                 "telemetry_drain", "ckpt_io", "other"]
        for name in order + sorted(set(comps) - set(order)):
            c = comps.get(name)
            if c is None:
                continue
            lines.append(
                f"{name:<18} {c['mean_ms']:>9.3f} {c['p50_ms']:>9.3f} "
                f"{c['p99_ms']:>9.3f} {100.0 * c['share']:>6.1f}%")
        if "wall_device_ratio_mean" in att:
            lines.append(
                f"  wall/device ratio: mean "
                f"{att['wall_device_ratio_mean']:.3f}, min "
                f"{att['wall_device_ratio_min']:.3f}")
        w = att.get("worst_step")
        if w is not None:
            parts = {k: v for k, v in w.items()
                     if k.endswith("_ms") and k != "wall_ms"
                     and isinstance(v, (int, float)) and v > 0.0}
            top = sorted(parts.items(), key=lambda kv: -kv[1])[:3]
            lines.append(
                f"  worst step: {w['step']} at "
                f"{_fmt(w['wall_ms'], 2)} ms ("
                + ", ".join(f"{k[:-3]} {_fmt(v, 2)}" for k, v in top)
                + ")")

    srv = summary.get("serving")
    if srv:
        lines.append("")
        head = (f"serving: {srv.get('submitted', 0)} submitted, "
                f"{srv.get('done', 0)} done, "
                f"{srv.get('preempted', 0)} preempted")
        if srv.get("deadline_exceeded"):
            head += f", {srv['deadline_exceeded']} deadline-expired"
        if srv.get("shed"):
            head += f", {srv['shed']} shed"
        rej = srv.get("rejected")
        if rej:
            head += (", rejected "
                     + " ".join(f"{k}={v}"
                                for k, v in sorted(rej.items())))
        lines.append(head)
        reps = srv.get("replicas")
        if reps:
            lines.append(
                "  fleet replicas: "
                + "  ".join(
                    f"{rid}: {row['submitted']} submitted / "
                    f"{row['terminal']} terminal"
                    + ("" if row["submitted"] == row["terminal"]
                       else "  [MISMATCH]")
                    for rid, row in reps.items()))
        fleet = srv.get("fleet")
        if fleet:
            lines.append(
                f"  fleet: {fleet['routed']} routed, "
                f"{fleet['kv_handoffs']} KV handoff(s), "
                f"{fleet['swaps']} rolling swap(s), "
                f"{fleet['replica_restarts']} replica restart(s)")
        cp = srv.get("control_plane")
        if cp:
            head = (f"  control plane: {cp['spawned']} spawned / "
                    f"{cp['reaped']} reaped"
                    + ("" if cp["spawned"] == cp["reaped"]
                       else "  [UNPAIRED]"))
            if cp.get("replayed_requests"):
                head += (f", {cp['replayed_requests']} request(s) "
                         f"journal-replayed")
            if cp.get("rpc_timeouts"):
                head += f", {cp['rpc_timeouts']} RPC timeout(s)"
            if cp.get("handoff_cold_fallbacks"):
                head += (f", {cp['handoff_cold_fallbacks']} cold "
                         f"prefill fallback(s)")
            lines.append(head)
            for r in cp.get("restarts", []):
                lines.append(
                    f"    RESTART {r.get('replica')} @ round "
                    f"{r.get('round')} [{r.get('reason')}] after "
                    f"{_fmt(r.get('backoff_s'), 3)}s backoff")
            shed = cp.get("shed_admission")
            if shed:
                lines.append(
                    "    QoS admission shed: "
                    + " ".join(f"{k}={v}"
                               for k, v in sorted(shed.items())))
            scale = cp.get("autoscale")
            if scale:
                lines.append(f"  autoscale trace ({len(scale)} "
                             f"event(s)):")
                for a in scale:
                    lines.append(
                        f"    round {a.get('round')}: "
                        f"{str(a.get('action')).upper():<4} "
                        f"{a.get('replica')} [{a.get('reason')}] "
                        f"backlog {_fmt(a.get('backlog'), 2)} -> "
                        f"{a.get('replicas')} replica(s)")
        for r in srv.get("journal_replays", []):
            lines.append(f"  JOURNAL REPLAY @ tick {r.get('tick')}: "
                         f"{r.get('replayed')} request(s) re-entered, "
                         f"{r.get('skipped_terminal')} already "
                         f"terminal")
        dists = srv.get("latency") or {}
        if dists:
            lines.append(f"{'series':<16} {'mean ms':>9} {'p50 ms':>9} "
                         f"{'p90 ms':>9} {'p99 ms':>9} {'n':>6}")
            for name in ("queue_wait_ms", "ttft_ms", "itl_ms"):
                d = dists.get(name)
                if d is None:
                    continue
                lines.append(
                    f"{name[:-3]:<16} {d['mean']:>9.3f} "
                    f"{d['p50']:>9.3f} {d['p90']:>9.3f} "
                    f"{d['p99']:>9.3f} {d['n']:>6}")
        if "pool_high_water_blocks" in srv:
            share = srv.get("pool_high_water_share")
            lines.append(
                f"  pool high water: "
                f"{srv['pool_high_water_blocks']} block(s)"
                + (f" ({100.0 * share:.0f}% of pool)"
                   if share is not None else ""))
        bt = srv.get("bucket_ticks")
        if bt:
            lines.append("  ticks per bucket: "
                         + " ".join(f"{k}={v}"
                                    for k, v in sorted(bt.items())))
        for s in srv.get("snapshots", []):
            lines.append(f"  SNAPSHOT @ tick {s.get('tick')} "
                         f"[{s.get('reason')}]: "
                         f"{s.get('active')} active, "
                         f"{s.get('queued')} queued")
        slo = srv.get("slo")
        if slo:
            lines.append(
                f"  SLO: {slo.get('burn_episodes', 0)} burn "
                f"episode(s), {slo.get('recoveries', 0)} "
                f"recovery(ies)")
            objs = (slo.get("objectives") or {}).get("objectives")
            if objs:
                for o in objs:
                    parts = [f"{k}={v}" for k, v in sorted(o.items())
                             if k != "priority_class" and v]
                    lines.append(
                        f"    objective [{o.get('priority_class')}]: "
                        + " ".join(parts))
            for b in slo.get("burns", []):
                lines.append(
                    f"    BURN @ tick {b.get('tick')} "
                    f"[{b.get('class')}/{b.get('dimension')}]: "
                    f"fast {_fmt(b.get('burn_fast'), 2)}x / "
                    f"slow {_fmt(b.get('burn_slow'), 2)}x budget")
        if srv.get("fleet_ticks"):
            lines.append(f"  fleet aggregation: "
                         f"{srv['fleet_ticks']} fleet_tick round(s)")
        ms = srv.get("metrics_server")
        if ms:
            lines.append(
                f"  metrics server: {ms['started']} started / "
                f"{ms['stopped']} stopped"
                + ("" if ms["started"] == ms["stopped"]
                   else "  [UNPAIRED]"))

    caps = summary.get("captures")
    if caps:
        lines.append("")
        lines.append(f"captured traces ({len(caps['windows'])} "
                     f"window(s), {caps['requested']} request(s)):")
        for c in caps["windows"]:
            # stopped_at None = the close()-time stop of a window that
            # was still open when the run tore down (its step-less
            # capture_stopped event)
            lines.append(
                f"  step {c.get('step')} [{c.get('reason')}] -> "
                f"{c.get('trace_dir')}"
                + (f" (closed @ {c['stopped_at']})"
                   if c.get("stopped_at") is not None
                   else " (open at exit)"))

    timers = summary.get("timers")
    if timers:
        lines.append("")
        lines.append(f"{'phase':<24} {'count':>6} {'total s':>10} "
                     f"{'mean ms':>10}")
        for name in sorted(timers):
            t = timers[name]
            lines.append(f"{name:<24} {t['count']:>6} "
                         f"{t['total_s']:>10.3f} {t['mean_ms']:>10.2f}")
    return "\n".join(lines)


def main(argv: Optional[List[str]] = None) -> int:
    """CLI: ``monitor_summary.py RUN.jsonl [--chrome OUT.json]`` —
    exit 0 on a parseable log (alarms are reported, not fatal), 1 on
    missing/empty input, 2 on usage error.  ``--chrome`` additionally
    rebuilds a Perfetto-loadable Chrome trace from the log's span and
    timer events (:func:`apex_tpu.monitor.tracing.
    chrome_trace_from_events`)."""
    argv = sys.argv[1:] if argv is None else argv
    chrome = None
    if "--chrome" in argv:
        i = argv.index("--chrome")
        if i + 1 >= len(argv):
            print("monitor_summary: --chrome needs a path",
                  file=sys.stderr)
            return 2
        chrome = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
    if not argv or argv[0] in ("-h", "--help"):
        print("usage: monitor_summary.py RUN.jsonl [MORE.jsonl ...] "
              "[--chrome OUT.json]   (several per-replica fleet logs "
              "merge into one summary)", file=sys.stderr)
        return 2
    events, malformed = [], 0
    try:
        for path in argv:
            evs, bad = load_events(path)
            events.extend(evs)
            malformed += bad
    except OSError as e:
        print(f"monitor_summary: {e}", file=sys.stderr)
        return 1
    if not events:
        print(f"monitor_summary: no events in {' '.join(argv)}",
              file=sys.stderr)
        return 1
    print(render(summarize(events, malformed)))
    if chrome is not None:
        from .tracing import chrome_trace_from_events, write_chrome_trace

        write_chrome_trace(chrome, chrome_trace_from_events(events))
        print(f"\nchrome trace -> {chrome}")
    return 0
