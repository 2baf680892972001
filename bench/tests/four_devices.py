"""The tiny training cell at dp 1 × sp 4, on four CPU devices, with a
fault planted or none; one JSON line per case.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
        python3 bench/tests/four_devices.py sound half_batch no_exchange

The device count is fixed when JAX starts, so ``test_layouts.py`` runs
this in a process of its own.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(cases) -> int:
    import jax
    import pytest

    from bench.tests import harness

    if len(jax.devices()) != 4:
        print(f"needs 4 devices, JAX sees {len(jax.devices())}",
              file=sys.stderr)
        return 1
    for case in cases:
        with pytest.MonkeyPatch.context() as mp:
            if case != "sound":
                harness.plant(case, mp.setattr)
            r = harness.run("train", traffic=harness.SP4, chips=4)
        print(json.dumps({"case": case, "correct": r["correct"],
                          "check": r["check"],
                          "count": r["device"]["count"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
