#!/usr/bin/env python
"""Assert the serving CLIs' --help stays in sync with the code.

Checks, for both ``python -m repro.launch.serve`` and
``examples/drift_serve.py``:

* every operating point in ``core.dvfs.OP_LADDER`` is named in the help
  text (the CLIs derive it from the ladder programmatically -- this guard
  catches someone replacing that with a stale literal);
* every scheduling/streaming/offload flag the docs advertise is present,
  and ``--smoke/--no-smoke`` (the switch to published widths);
* the ``--rollback-interval`` help renders its default from
  ``core.rollback.DEFAULT_INTERVAL`` (the single source of truth -- the
  old CLIs duplicated the literal 10 in help strings, which is exactly
  the drift this script exists to catch);
* the ``--arch`` help names every registered config grouped by serving
  paradigm (derived from the ServableModel registry via
  ``launch.serve.arch_family_help`` -- adding a config without wiring its
  family into the registry, or hard-coding a stale arch list, fails here).

Run from the repo root (CI does: the docs job in
.github/workflows/ci.yml):

    PYTHONPATH=src python tools/check_help_sync.py
"""
import subprocess
import sys

sys.path.insert(0, "src")
from repro import configs  # noqa: E402
from repro.core.dvfs import OP_LADDER  # noqa: E402
from repro.core.rollback import DEFAULT_INTERVAL  # noqa: E402

CLIS = (
    [sys.executable, "-m", "repro.launch.serve", "--help"],
    [sys.executable, "examples/drift_serve.py", "--help"],
)
REQUIRED_FLAGS = ("--op", "--priority", "--deadline", "--step-budget",
                  "--stream", "--batch", "--steps", "--arch",
                  "--metrics-port", "--no-telemetry",
                  "--rollback-interval", "--offload",
                  "--energy-budget", "--quality-floor", "--trace-dir",
                  "--smoke", "--no-smoke")
# --arch help must be registry-derived: every registered config by name,
# plus the paradigm labels the registry groups them under.
PARADIGM_WORDS = ("diffusion", "autoregressive", "unsupported")
# The rendered interval default must come from the one constant (a CLI
# hard-coding the number would go stale the day the constant moves).
INTERVAL_DEFAULT_TEXT = f"default: {DEFAULT_INTERVAL},"


def main() -> int:
    failures = []
    for cmd in CLIS:
        out = subprocess.run(cmd, capture_output=True, text=True,
                             check=True).stdout
        missing = [p.name for p in OP_LADDER if p.name not in out]
        missing += [f for f in REQUIRED_FLAGS if f not in out]
        missing += [a for a in configs.list_archs() if a not in out]
        missing += [w for w in PARADIGM_WORDS if w not in out]
        if INTERVAL_DEFAULT_TEXT not in out:
            missing.append(f"'{INTERVAL_DEFAULT_TEXT}' (rollback-interval "
                           "default derived from rollback.DEFAULT_INTERVAL)")
        if missing:
            failures.append((cmd, missing))
        else:
            print(f"ok: {' '.join(cmd[-2:])} help names the full ladder, "
                  f"all scheduler/offload flags, every registered arch "
                  f"by paradigm, and the DEFAULT_INTERVAL-derived default")
    for cmd, missing in failures:
        print(f"FAIL {' '.join(cmd)}: --help missing {missing}",
              file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
