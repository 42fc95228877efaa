"""The controls of ``kind: serve_open_loop_routed``'s three limits on
the ``openpangu-ultra-moe`` job: ``control_routed``'s float8, change and
planted-token samples, as it stands, with this configuration's builder
and reference where that module names Laguna's (its file may not be
edited to take them as arguments; the stand-in lasts the process).  Run
once, by hand, on the chip, when a cell of this configuration is defined
(``PERF.md`` holds the readings); no part of a measured run.

    python3 -m benchmarks.control_openpangu \
        --workload openpangu-ultra-moe.serve-reason-sat --seed 7 \
        --seconds 20
"""
from __future__ import annotations

import sys

from . import builders_openpangu, control_routed, reference_openpangu


def main(argv=None) -> int:
    control_routed.builders_laguna = builders_openpangu
    control_routed.reference_laguna = reference_openpangu
    return control_routed.main(argv)


if __name__ == "__main__":
    sys.exit(main())
