#!/usr/bin/env python
"""Render an apex_tpu.monitor JSONL event log as a run-health summary.

    python tools/monitor_summary.py RUN.jsonl

Prints throughput / loss trajectory / amp overflow history / watchdog
alarms / phase-timer totals.  Exit 0 on a
parseable log (alarms are reported, not fatal), non-zero on a missing
or empty one — CI keys off that (tools/ci.sh monitor smoke).  See
docs/api/observability.md for the schema.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from apex_tpu.monitor.summary import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
