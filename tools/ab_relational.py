"""A/B measurement of the round-4 scatter-free relational redesign.

Round 4 rewrote ops/aggregate.py + ops/join.py around measured primitive
costs but shipped no number. This tool produces the
number: it checks out the pre-redesign tree (round-3 final, the last commit
with the searchsorted/scatter design) into a git worktree and runs the SAME
bench harness (benchmarks/bench_groupby.py + bench_join.py, byte-identical
between the two revisions — verified with `git diff 123f6ad HEAD`) against
both implementations, on the same backend, in fresh subprocesses.

BASELINE.json shapes: configs[1] groupby sum/count, single int32 key, 10M
rows (also the 100-key variant); configs[2] inner join 10M x 1M int64 keys.

Usage:
    python tools/ab_relational.py [--scale 1.0] [--iters 5] [--device]
                                  [--old-rev 123f6ad]
Appends one record per (impl, bench, axes) to tools/ab_relational.jsonl and
prints a speedup summary. Default backend is CPU (`--cpu` benches);
--device drops the pin for the real-chip capture.
"""
import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OLD_WT = os.path.join(REPO, ".ab_old")
BENCHES = ("benchmarks/bench_groupby.py", "benchmarks/bench_join.py")


def ensure_worktree(rev: str) -> str:
    if not os.path.isdir(OLD_WT):
        subprocess.run(["git", "worktree", "add", "--detach", OLD_WT, rev],
                       cwd=REPO, check=True, capture_output=True)
    head = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                          cwd=OLD_WT, check=True, capture_output=True,
                          text=True).stdout.strip()
    return head


def run_tree(root: str, impl: str, rev: str, args) -> list:
    recs = []
    env = dict(os.environ)
    for bench in BENCHES:
        cmd = [sys.executable, bench, "--scale", str(args.scale),
               "--iters", str(args.iters)]
        if not args.device:
            cmd.append("--cpu")
        r = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                           text=True, timeout=3600)
        if r.returncode != 0:
            print(f"FAIL {impl} {bench}: {r.stderr[-500:]}", file=sys.stderr)
            continue
        for line in r.stdout.splitlines():
            line = line.strip()
            if line.startswith("{"):
                rec = json.loads(line)
                rec.update({"impl": impl, "rev": rev,
                            "backend": "device" if args.device else "cpu"})
                recs.append(rec)
                print(json.dumps(rec), flush=True)
    return recs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--device", action="store_true",
                    help="measure on the default (TPU) backend instead of CPU")
    ap.add_argument("--old-rev", default="123f6ad",
                    help="pre-redesign revision (round-3 final)")
    ap.add_argument("--out", default=os.path.join(REPO, "tools",
                                                  "ab_relational.jsonl"))
    args = ap.parse_args(argv)

    old_rev = ensure_worktree(args.old_rev)
    new_rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             cwd=REPO, check=True, capture_output=True,
                             text=True).stdout.strip()
    recs = run_tree(OLD_WT, "old", old_rev, args)
    recs += run_tree(REPO, "new", new_rev, args)

    with open(args.out, "a") as f:
        for r in recs:
            f.write(json.dumps(r) + "\n")

    # speedup summary: match (bench, axes) pairs across impls
    def key(r):
        return (r["bench"], json.dumps(r["axes"], sort_keys=True))
    old = {key(r): r for r in recs if r["impl"] == "old"}
    new = {key(r): r for r in recs if r["impl"] == "new"}
    for k in sorted(old.keys() & new.keys()):
        sp = old[k]["ms"] / new[k]["ms"]
        print(f"SPEEDUP {k[0]} {k[1]}: old {old[k]['ms']:.1f} ms -> "
              f"new {new[k]['ms']:.1f} ms  ({sp:.2f}x)")


if __name__ == "__main__":
    main()
