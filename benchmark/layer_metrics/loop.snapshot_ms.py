"""loop.snapshot_ms (ms): what the train thread spends in host snapshots of
the parameters (`Trainer._take_snapshot`) per launch-to-launch period. Layer:
train loop. Moves round_tok_s_chip.

Summed durations of every `loop.snapshot` span that started in the window,
whatever its trace id: the one at each cadence boundary (trace `loop`) and
the one inside the merge (the round's key; `loop.merge_ms` counts that one
too), over the number of whole periods the window held."""


def compute(run):
    periods = run["stats"].get("rounds.in_window")
    durs = [s["dur_s"] for s in run["spans"]
            if s["name"] == "loop.snapshot" and s.get("dur_s") is not None]
    if not periods or not durs:
        return None
    return sum(durs) / periods * 1e3
