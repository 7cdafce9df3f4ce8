"""The generator's seed discipline: the same seed gives the same inputs,
and every seed the same amount of work; seeds past 32 bits work."""

import traffic

SEEDS = (0, 7, 2**31 + 5, 2**40 + 3)


def test_proof_log_repeats_per_seed_and_counts_are_fixed():
    mix = dict(traffic.load("replay-1pct"), records=400, statements=8,
               reject_frac=0.05, lie_frac=0.01)
    for seed in (3, 2**31 + 7):
        recs, wrong, lie = traffic.proof_log(mix, seed)
        again, _, _ = traffic.proof_log(mix, seed)
        assert recs == again
        assert (len(recs), len(wrong), len(lie)) == (400, 20, 4)
        assert sum(r["v"] == 0 for r in recs) == len(wrong ^ lie)


def test_rejects_reach_every_quantum_across_the_plausible_range():
    """The cell's why: a rejected proof in (nearly) every 4,096-record
    quantum, so each runs the combined check and then the per-row fallback.
    At the cell's 1% every quantum holds one; at 0.1%, 98% do (PERF.md 4)."""
    mix = traffic.load("replay-1pct")
    quantum = 4096
    quanta = mix["records"] // quantum
    for frac, least in ((0.01, quanta), (0.001, quanta - 2)):
        for seed in SEEDS:
            wrong, _ = traffic.marked(dict(mix, reject_frac=frac), seed)
            hit = {i // quantum for i in wrong}
            assert len(hit) >= least, (frac, seed, len(hit))
