"""Turn a hardware sweep (.hw/) into calibration DECISIONS.

VERDICT r3 item 2: "calibrate from measurement, then delete the losers."
The sweep (.hardware_sweep.sh) measures; this script reads its outputs
and prints the verdicts the flags are waiting on:

- CPZK_MSM_WINDOW    — best measured window vs `msm.pick_window`'s model;
- PIPPENGER_MIN_ROWS — rowcombined/pippenger crossover from the small-N
  bench points vs the 16k/64k points;
- CPZK_PALLAS        — graduate (make default) or drop, from the
  point-op A/B.

Usage: python benches/calibrate.py [dir]   (default .hw)
Prints a PROFILE.md-ready section; exits 1 when the sweep is too
incomplete to decide anything (so automation notices).
"""

from __future__ import annotations

import json
import os
import re
import sys


def _records(path: str):
    try:
        with open(path, encoding="utf-8") as f:
            for line in f:
                line = line.strip()
                if line:
                    try:
                        yield json.loads(line)
                    except json.JSONDecodeError:
                        continue
    except OSError:
        return


def _value(path: str, metric: str | None = None) -> float | None:
    for rec in _records(path):
        if metric is None or rec.get("metric") == metric or rec.get("name") == metric:
            v = rec.get("value")
            if isinstance(v, (int, float)) and v > 0:
                return float(v)
    return None


def main() -> None:
    d = sys.argv[1] if len(sys.argv) > 1 else ".hw"
    if not os.path.isdir(d):
        raise SystemExit(f"no sweep directory {d!r}")
    decided = 0
    print("## Hardware calibration (from the sweep in %s)\n" % d)

    # 1. window sweep
    wins: dict[int, float] = {}
    for name in os.listdir(d):
        m = re.fullmatch(r"win_(\d+)\.json", name)
        if m:
            v = _value(os.path.join(d, name))
            if v:
                wins[int(m.group(1))] = v
    if wins:
        best_w = max(wins, key=lambda w: wins[w])
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "msm", os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "cpzk_tpu", "ops", "msm.py"))
        model_w = None
        try:
            sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
            from cpzk_tpu.ops import msm

            model_w = msm.pick_window(4 * 16384 + 2)
        except Exception:
            pass
        print(f"- **CPZK_MSM_WINDOW**: measured best c={best_w} "
              f"({wins[best_w]:.0f} proofs/s at 16k; all: "
              f"{ {w: round(v) for w, v in sorted(wins.items())} }); "
              f"cost model picks c={model_w}.")
        if model_w is not None and model_w != best_w:
            print(f"  -> FIX `msm.pick_window` so the model lands on "
                  f"c={best_w} at m=4*16384+2, then delete the env knob "
                  "from the serving docs.")
        else:
            print("  -> model agrees; keep it, drop the knob from docs.")
        decided += 1
    else:
        print("- CPZK_MSM_WINDOW: no win_*.json points yet.")

    # 2. crossover
    small = {n: _value(os.path.join(d, f"cross_{n}.json")) for n in (1024, 4096)}
    big = {n: _value(os.path.join(d, f"bench_{n//1024}k.json")) for n in (16384, 65536)}
    have = {**{k: v for k, v in small.items() if v},
            **{k: v for k, v in big.items() if v}}
    if have:
        print(f"- **PIPPENGER_MIN_ROWS**: measured proofs/s by N: "
              f"{ {n: round(v) for n, v in sorted(have.items())} } "
              "(auto mode records the faster of rowcombined/pippenger; "
              "per-kernel rows are in the .err/.json files).")
        print("  -> set PIPPENGER_MIN_ROWS to the smallest N where the "
              "pippenger kernel wins its A/B.")
        decided += 1
    else:
        print("- PIPPENGER_MIN_ROWS: no crossover points yet.")

    # 3. pallas graduate-or-drop
    xla = _value(os.path.join(d, "point_xla.json"))
    pal = _value(os.path.join(d, "point_pallas.json"))
    if xla and pal:
        ratio = pal / xla
        verdict = "GRADUATE (make default)" if ratio >= 1.1 else (
            "DROP (delete ops/pallas_kernels.py + the flag)" if ratio <= 0.95
            else "keep behind the flag (within noise)")
        print(f"- **CPZK_PALLAS**: pallas/xla point-op ratio {ratio:.2f} "
              f"-> {verdict}.")
        decided += 1
    else:
        print("- CPZK_PALLAS: missing point_xla/point_pallas A/B.")

    if decided == 0:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
