"""Multi-PROCESS mesh proof: the distributed tier over jax.distributed.

Everything in parallel/ runs as SPMD programs over a Mesh; the v5p-64 north
star (SURVEY.md §2.4) is a MULTI-HOST mesh, where the same programs execute
with each host driving only its local chips and XLA collectives riding
ICI/DCN between them. This tool proves that path end to end on CPU: it
spawns N worker processes, each `jax.distributed.initialize`d with
--xla_force_host_platform_device_count local CPU devices, builds the GLOBAL
8-device mesh, feeds process-local shards via
jax.make_array_from_process_local_data, and runs the distributed relational
tier (groupby → ICI all-to-all → final agg; hash-exchange inner join; the
typed-key semi join) exactly as the single-process dryrun does — same code,
multi-process runtime (the reference's analogue: its NCCL/UCX shuffle runs
one rank per executor process).

Usage:
    python tools/multiproc_mesh.py                 # orchestrate 2x4 procs
    python tools/multiproc_mesh.py --worker PID    # internal
Exit 0 and one "MULTIPROC MESH OK" line per worker on success.
"""
import argparse
import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# geometry is env-parametrized so CI can prove N>2 processes too
# (default 2x4; the v5p north star is 16 hosts x 4 chips)
N_PROCS = int(os.environ.get("SRT_MULTIPROC_PROCS", "2"))
LOCAL_DEVICES = int(os.environ.get("SRT_MULTIPROC_LOCAL_DEVICES", "4"))


def worker(pid: int, port: int) -> None:
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=N_PROCS,
                               process_id=pid)
    assert len(jax.local_devices()) == LOCAL_DEVICES, jax.local_devices()
    n_dev = N_PROCS * LOCAL_DEVICES
    assert jax.device_count() == n_dev, jax.device_count()

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    sys.path.insert(0, REPO)
    from spark_rapids_tpu.parallel import (distributed_groupby,
                                           distributed_inner_join,
                                           distributed_left_semi_join_keyed,
                                           encode_key_columns)
    from spark_rapids_tpu import Column, dtypes

    mesh = Mesh(np.array(jax.devices()).reshape(n_dev), ("data",))
    sh = NamedSharding(mesh, P("data"))
    n = 16 * n_dev                       # global rows

    def dist(host_global):
        """Global array from this process's slice of host data (each
        process feeds only its own rows — the multi-host ingestion path)."""
        m = len(host_global)
        chunk = m // N_PROCS
        lo = pid * chunk
        return jax.make_array_from_process_local_data(
            sh, np.asarray(host_global[lo:lo + chunk]), (m,))

    keys_h = (np.arange(n) % 7).astype(np.int64)
    vals_h = np.arange(n, dtype=np.int64)
    keys, vals = dist(keys_h), dist(vals_h)

    # distributed groupby: partial agg -> all-to-all by key hash -> final
    gk, (gsum, gcnt), gvalid, overflow = distributed_groupby(
        mesh, keys, vals, ["sum", "count"], key_cap=16)
    groups, total, ssum, ovf = jax.jit(
        lambda v, c, s, o: (jnp.sum(v.astype(jnp.int32)),
                            jnp.sum(jnp.where(v, c, 0)),
                            jnp.sum(jnp.where(v, s, 0)),
                            jnp.any(o)))(gvalid, gcnt, gsum, overflow)
    assert not bool(ovf)
    assert int(groups) == 7 and int(total) == n, (int(groups), int(total))
    assert int(ssum) == int(vals_h.sum())

    # distributed inner join (hash exchange both sides)
    rk = dist(np.arange(0, n, 2, dtype=np.int64) % 7)
    rv = dist(np.arange(n // 2, dtype=np.int64))
    _, _, _, ivalid, iover = distributed_inner_join(
        mesh, keys, vals, rk, rv, row_cap=2 * n * n // 7,
        slack=float(n_dev))
    jrows, jovf = jax.jit(lambda v, o: (jnp.sum(v.astype(jnp.int64)),
                                        jnp.any(o)))(ivalid, iover)
    assert not bool(jovf)
    # every left row matches n/2/7-ish right rows; exact count from numpy
    import collections
    rcnt = collections.Counter((np.arange(0, n, 2) % 7).tolist())
    want = sum(rcnt[int(k)] for k in keys_h)
    assert int(jrows) == want, (int(jrows), want)

    # typed tier: string keys through the word codec + Spark-exact hash
    vocab = ["apple", "banana", "", "cherry"]
    scol = Column.from_pylist([vocab[i % 4] for i in range(n)], dtypes.STRING)
    words, specs = encode_key_columns([scol], max_bytes=[8])
    l_words = [dist(np.asarray(w)) for w in words]
    r_words = [dist(np.asarray(w[::2])) for w in words]   # evens: all vocab
    lv = dist(np.arange(n, dtype=np.int64))
    _, _, svalid, sover = distributed_left_semi_join_keyed(
        mesh, l_words, [lv], r_words, specs, slack=float(n_dev))
    srows, sovf = jax.jit(lambda v, o: (jnp.sum(v.astype(jnp.int64)),
                                        jnp.any(o)))(svalid, sover)
    assert not bool(sovf)
    # right side holds the even-indexed rows, i.e. vocab[0] and vocab[2]
    # only -> exactly the even-vocab half of the left side matches
    assert int(srows) == n // 2, int(srows)

    # one write, newline included: the workers share the orchestrator's
    # stdout, and `print` writes its end apart (two workers' lines merged
    # into one under an unbuffered stdout and the test counted one)
    sys.stdout.write(
        f"MULTIPROC MESH OK proc={pid}/{N_PROCS} devices={n_dev} "
        f"groups={int(groups)} join_rows={int(jrows)} semi={int(srows)}\n")
    sys.stdout.flush()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _run_once(timeout_s: float) -> int:
    """Spawn the workers, wait with a shared deadline, ALWAYS reap them
    (a worker stuck in a distributed barrier must not outlive its failed
    peer, hold the inherited stdout pipe open, or pin the CPU devices)."""
    import time
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{LOCAL_DEVICES}").strip()
    port = _free_port()
    procs = []
    rc = 0
    try:
        procs = [subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker", str(i),
             "--port", str(port)], env=env, cwd=REPO)
            for i in range(N_PROCS)]
        deadline = time.monotonic() + timeout_s
        for i, p in enumerate(procs):
            try:
                p.wait(timeout=max(1.0, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                print(f"worker {i} TIMED OUT after {timeout_s:.0f}s",
                      file=sys.stderr)
                rc = 1
                break
            if p.returncode != 0:
                print(f"worker {i} FAILED rc={p.returncode}",
                      file=sys.stderr)
                rc = 1
                break                     # kill the peer in finally: it is
                #                           blocked on a collective barrier
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", type=int, default=None)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=480.0,
                    help="per-attempt deadline for all workers")
    args = ap.parse_args(argv)
    if args.worker is not None:
        worker(args.worker, args.port)
        return 0
    rc = _run_once(args.timeout)
    if rc != 0:
        # one retry on a fresh port: _free_port is inherently TOCTOU (the
        # port is released before the coordinator binds it) and a busy CI
        # host can steal it in the window
        print("retrying once on a fresh port", file=sys.stderr)
        rc = _run_once(args.timeout)
    return rc


if __name__ == "__main__":
    sys.exit(main())
