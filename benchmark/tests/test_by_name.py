"""A cell is added by data: a configuration, a traffic mix and a metric
dropped in as new files, with entries in BENCHMARK.json, are found by name
with no edit to any file the benchmark already has."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path):
    copy = tmp_path / "checkout"
    shutil.copytree(BENCH, copy / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), copy / "BENCHMARK.json")
    before = _digests(copy / "benchmark")

    cfg = json.load(open(copy / "benchmark/configs/audit-log.json"))
    cfg["audit"]["quantum"] = 4095
    (copy / "benchmark/configs/audit-log-q4095.json").write_text(json.dumps(cfg))
    # a mix that only changes parameters the generator already reads
    mix = json.load(open(copy / "benchmark/traffic/replay-1pct.json"))
    mix.update(reject_frac=0.0, lie_frac=0.0)
    (copy / "benchmark/traffic/replay-allvalid.json").write_text(json.dumps(mix))
    (copy / "benchmark/metrics/settled_proofs.py").write_text(
        "def read(art):\n    return art.get('settled')\n")
    bench = json.load(open(copy / "BENCHMARK.json"))
    bench["configs"].append(dict(bench["configs"][0], name="audit-log-q4095",
                                 file="benchmark/configs/audit-log-q4095.json"))
    bench["workloads"].append({"name": "audit-log-q4095.replay-allvalid",
                               "config": "audit-log-q4095",
                               "traffic": "replay-allvalid", "chips": 1,
                               "why": "test"})
    # an end-to-end metric of some cells lists the new one too (an addition)
    next(m for m in bench["end_to_end"] if m["name"] == "verified_per_s")[
        "workloads"].append("audit-log-q4095.replay-allvalid")
    bench["per_layer"].append({"name": "settled_proofs", "unit": "proofs",
                               "better": "higher", "source": "program_counter",
                               "layer": "audit pipeline",
                               "moves": "verified_per_s",
                               "workloads": ["audit-log-q4095.replay-allvalid"]})
    (copy / "BENCHMARK.json").write_text(json.dumps(bench))

    probe = f"""
import sys
sys.path[:0] = [{str(copy / 'benchmark')!r}, {ROOT!r}]
import harness, traffic
bench = harness.load_benchmark()
cell = {{w['name']: w for w in bench['workloads']}}['audit-log-q4095.replay-allvalid']
cfg = harness.load_config(cell['config'])
mix = traffic.load(cell['traffic'])
assert cfg['audit']['quantum'] == 4095 and cfg['driver'] == 'audit_replay'
names = [m['name'] for m in harness.metrics_of(bench, cell['name'], False)]
assert sorted(names) == ['setup_s', 'verified_per_s'], names
names = [m['name'] for m in harness.metrics_of(bench, cell['name'], True)]
assert names == ['settled_proofs'], names
reader = harness.load_module('metrics', 'settled_proofs')
assert reader.read({{'settled': 28}}) == 28
harness.load_module('drivers', cfg['driver'])
recs, wrong, lie = traffic.proof_log(dict(mix, records=16, statements=4),
                                     2**31 + 99)
assert len(recs) == 16 and not wrong and not lie
assert all(r['v'] == 1 for r in recs)
print('ok')
"""
    out = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                         text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == "ok", out.stderr
    after = _digests(copy / "benchmark")
    assert {k: v for k, v in after.items() if k in before} == before
