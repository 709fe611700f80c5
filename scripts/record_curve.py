"""Convert a tracker metrics.jsonl run into a committed curve artifact.

The learning-curve protocol (parity: ref trlx/reference.py — W&B curve
diffing) keeps a recorded reward-vs-step JSONL under docs/curves/ so
regressions diff against a committed artifact instead of a prose claim.
This script trims a raw tracker log (utils/trackers.py) down to the
curve-relevant keys and prepends a meta line.

Usage:
    python scripts/record_curve.py /tmp/run/metrics.jsonl \
        docs/curves/randomwalks_ilql.jsonl \
        --task "randomwalks ILQL (examples/randomwalks/ilql_randomwalks.py)" \
        --protocol "offline ILQL, 1000 steps, eval every 100" \
        --keys reward/mean metrics/optimality losses/loss
"""

from __future__ import annotations

import argparse
import json
import time


def _live_hardware() -> str:
    import jax

    devices = jax.devices()
    return f"{len(devices)}x {devices[0].device_kind}"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("src")
    ap.add_argument("dst")
    ap.add_argument("--task", required=True)
    ap.add_argument("--protocol", required=True)
    ap.add_argument(
        "--hardware", default=None,
        help="what the run was recorded on (default: the live device, "
        "'<count>x <device_kind>' as JAX reports it)",
    )
    ap.add_argument(
        "--keys", nargs="+",
        default=["reward/mean", "metrics/optimality", "losses/loss"],
        help="metric keys to keep; a key K also keeps sweep variants K@...",
    )
    ap.add_argument(
        "--final-key", default="metrics/optimality",
        help="meta final_* value = last record carrying this key (or a sweep variant)",
    )
    ap.add_argument("--extra-meta", default="{}", help="JSON merged into the meta line")
    args = ap.parse_args()

    def keep(k: str) -> bool:
        return any(k == key or k.startswith(key + "@") for key in args.keys)

    rows, final = [], {}
    with open(args.src) as f:
        for line in f:
            rec = json.loads(line)
            kept = {k: round(v, 4) for k, v in rec.items() if keep(k)}
            if not kept:
                continue
            if "_step" not in rec:
                # a non-Tracker jsonl row defaulting to step 0 mid-file
                # would violate the monotonic-steps contract that
                # tests/test_curves.py enforces only AFTER the artifact
                # is committed — skip it at record time instead
                continue
            rows.append({"step": rec["_step"], **kept})
            fk = {
                k: v for k, v in kept.items()
                if k == args.final_key or k.startswith(args.final_key + "@")
            }
            if fk:
                final = fk

    meta = {
        "task": args.task,
        "protocol": args.protocol,
        "hardware": args.hardware or _live_hardware(),
        "date": time.strftime("%Y-%m-%d"),
        **{
            "final_" + k.split("/")[-1]: v
            for k, v in sorted(final.items())
        },
        "reference_protocol": "curve parity per ref trlx/reference.py",
        **json.loads(args.extra_meta),
    }
    with open(args.dst, "w") as f:
        f.write(json.dumps({"meta": meta}) + "\n")
        for row in rows:
            f.write(json.dumps(row) + "\n")
    print(f"wrote {args.dst}: {len(rows)} rows, meta={meta}")


if __name__ == "__main__":
    main()
