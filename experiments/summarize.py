#!/usr/bin/env python
"""Render the committed experiment artifacts into one markdown digest.

Reads ONLY what is on disk under experiments/results/ (the same artifacts
BASELINE.md cites) and prints a compact markdown summary — a cross-check
that the prose tables and the jsonl evidence agree, and a quick orientation
for reviewers. Missing artifacts are listed rather than fabricated.

Run: python experiments/summarize.py
"""

from __future__ import annotations

import json
import os

RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "results")


def _load_json(name):
    try:
        with open(os.path.join(RESULTS, name)) as fh:
            return json.load(fh)
    except (OSError, ValueError):
        return None


def _load_jsonl(name):
    try:
        with open(os.path.join(RESULTS, name)) as fh:
            return [json.loads(l) for l in fh if l.strip()]
    except (OSError, ValueError):
        return None


def main() -> int:
    missing = []

    print("# Experiment digest (generated from experiments/results/)\n")

    summary = _load_json("summary.json")
    if summary:
        print("## Config matrix (localhost swarms, real entrypoints)\n")
        print("| config | volunteers | finished | rounds ok/skip | crossed | time-to-target |")
        print("|---|---|---|---|---|---|")
        for key in sorted(summary):
            row = summary[key]
            if not isinstance(row, dict):
                continue
            if "volunteers" not in row:  # nested (config8) or derived rows
                for sub, r in row.items():
                    if isinstance(r, dict) and "volunteers" in r:
                        print(f"| {key}/{sub} | {r['volunteers']} | {r.get('finished')} "
                              f"| {r.get('rounds_ok_total')}/{r.get('rounds_skipped_total')} "
                              f"| {r.get('crossed', '—')} | {r.get('time_to_target_s_mean', '—')} |")
                continue
            print(f"| {key} | {row['volunteers']} | {row.get('finished')} "
                  f"| {row.get('rounds_ok_total', '—')}/{row.get('rounds_skipped_total', '—')} "
                  f"| {row.get('crossed', '—')} | {row.get('time_to_target_s_mean', '—')} |")
    else:
        missing.append("summary.json")

    wires = _load_jsonl("wire_bytes.jsonl")
    if wires:
        print("\n## Wire codecs (bytes/round/volunteer)\n")
        print("| wire | bytes | vs f32 | loss @ 8 rounds |")
        print("|---|---|---|---|")
        for w in wires:
            print(f"| {w['wire']} | {w['bytes_per_round_per_volunteer']:.0f} "
                  f"| {w['vs_f32']:.3f} | {w['final_loss_mean']:.3f} |")
    else:
        missing.append("wire_bytes.jsonl")

    psgd = _load_jsonl("psgd_compare.jsonl")
    if psgd:
        print("\n## Codec convergence horizon (gpt2 proxy, latest run)\n")
        print("| arm | final loss | WAN MB | rounds |")
        print("|---|---|---|---|")
        for r in psgd:
            if "arm" in r:
                print(f"| {r['arm']} | {r['final_loss_mean']:.3f} "
                      f"| {r['wan_bytes_total'] / 1e6:.2f} | {r['rounds_ok_total']} |")
    else:
        missing.append("psgd_compare.jsonl")

    s16 = _load_json("scale16.json")
    if s16:
        print("\n## Averaging tier at 16 volunteers\n")
        print("| arm | finished | rounds ok | min/volunteer |")
        print("|---|---|---|---|")
        for tag, agg in s16.items():
            print(f"| {tag} | {agg['finished']}/16 | {agg['rounds_ok_total']} "
                  f"| {agg.get('n_rounds_ok_min', '—')} |")
    else:
        missing.append("scale16.json")

    soak = _load_jsonl("soak.jsonl")
    if soak:
        ok_rows = [r for r in soak if r.get("ok")]
        print(f"\n## Payload soaks: {len(ok_rows)} ok rows "
              f"(latest: {ok_rows[-1]['wire']} {ok_rows[-1]['seconds']}s "
              f"@ loadavg {ok_rows[-1].get('loadavg', '—')})")
    else:
        missing.append("soak.jsonl")

    if missing:
        print("\n## Missing artifacts\n")
        for m in missing:
            print(f"- {m}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
