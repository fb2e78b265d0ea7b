#!/usr/bin/env python
"""lint_jsonl: strict-JSON + schema linting for the repo's metrics rows.

    python scripts/lint_jsonl.py <file-or-dir> [...]

A line passes only if it parses as STRICT JSON — Python's json module
happily reads the bare ``NaN``/``Infinity`` tokens its own default dumps
emits, which is exactly the producer bug (pre-obs MetricsLogger) this
linter exists to catch, so those constants are rejected via
``parse_constant``.  Rows that carry a ``kind`` are additionally validated
against the obs/ schema (envelope keys + per-kind required keys,
obs/schema.py).

Importable: ``lint_line(line) -> Optional[str]`` and
``lint_file(path) -> List[str]`` are what the test suite and obs_report use.
Exit codes: 0 = clean, 1 = any error (each printed as ``path:line: why``).

The valid kind set is NOT maintained here: it is exactly
``obs/schema.py REQUIRED_KEYS`` (``KNOWN_KINDS``), validated with
``require_known_kind=True`` — so a chaos-soak, traced, net-smoke, or
league run dir lints against the same registry the emitters and the
golden-schema test use, and a kind can never be valid in one layer and
unknown in another (the replay-plane soak's ``replay_net`` rows —
`make replaynet-smoke` — lint through the same registry).  The static
config-drift analyzer
(rainbow_iqn_apex_tpu/analysis/configcheck.py) closes the loop from the
emission side: every ``logger.log("<kind>", ...)`` literal in the package
and scripts/ must name a registered kind, so registry and emitters move
in the same commit.  Replay-reuse runs (cfg.replay_ratio > 1) extend
``learn``/``health``/``lag`` rows with optional payload keys under the
same strict-JSON rules; rows that carry no ``kind`` (the load generator's,
scripts/bench_serve.py) skip schema validation by design.  The
telemetry-plane soak (`make obsnet-smoke`) lints its run dir the same way: relay/collector
lifecycle ``obs_net`` rows, SLO-edge ``alert`` rows, and the collector's
periodic ``fleet_health`` fold all validate through this one registry.
"""

from __future__ import annotations

import glob
import json
import os
import sys
from typing import List, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from rainbow_iqn_apex_tpu.obs.schema import validate_row  # noqa: E402


class _NonFinite(ValueError):
    pass


def _reject_constant(token: str):
    raise _NonFinite(f"non-finite JSON constant {token!r}")


def lint_line(line: str, check_schema: bool = True) -> Optional[str]:
    """None when the line is a valid strict-JSON row, else the error."""
    try:
        row = json.loads(line, parse_constant=_reject_constant)
    except _NonFinite as e:
        return str(e)
    except ValueError as e:
        return f"invalid JSON: {e}"
    if not isinstance(row, dict):
        return f"row is {type(row).__name__}, expected object"
    if check_schema and "kind" in row:
        # require_known_kind: the schema registry (obs/schema.py
        # REQUIRED_KEYS) is the ONE list of valid kinds — this linter
        # carries none of its own, so a kind added to the registry is valid
        # here in the same commit and an unregistered kind fails both the
        # static config-drift analyzer (emission side) and this lint
        # (consumption side)
        errs = validate_row(row, require_known_kind=True)
        if errs:
            return "; ".join(errs)
    return None


def lint_file(path: str, check_schema: bool = True) -> List[str]:
    errors = []
    with open(path) as fh:
        for lineno, line in enumerate(fh, 1):
            if not line.strip():
                continue
            err = lint_line(line, check_schema=check_schema)
            if err is not None:
                errors.append(f"{path}:{lineno}: {err}")
    return errors


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    if not args:
        print("usage: lint_jsonl.py <file-or-dir> [...]", file=sys.stderr)
        return 2
    paths: List[str] = []
    for arg in args:
        if os.path.isdir(arg):
            paths += sorted(
                glob.glob(os.path.join(arg, "**", "*.jsonl"), recursive=True)
            )
        else:
            paths.append(arg)
    if not paths:
        print("lint_jsonl: no .jsonl files found", file=sys.stderr)
        return 2
    total_errors = 0
    for path in paths:
        for err in lint_file(path):
            print(err)
            total_errors += 1
    print(f"lint_jsonl: {len(paths)} file(s), {total_errors} error(s)",
          file=sys.stderr)
    return 1 if total_errors else 0


if __name__ == "__main__":
    raise SystemExit(main())
