"""Multi-process mesh proof as a test: tools/multiproc_mesh.py spawns N
jax.distributed processes and runs the distributed relational tier over the
GLOBAL 8-device mesh — the multi-host north-star path (SURVEY.md §2.4).
Subprocess-orchestrated because jax.distributed can initialize only once
per process; the workers must not inherit this test process's
single-process JAX env (or a caller's SRT_MULTIPROC_* geometry)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(procs: str, local: str):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS",
                        "SRT_MULTIPROC_PROCS", "SRT_MULTIPROC_LOCAL_DEVICES")}
    env["SRT_MULTIPROC_PROCS"] = procs
    env["SRT_MULTIPROC_LOCAL_DEVICES"] = local
    # tool deadline < subprocess timeout: one attempt + the fresh-port retry
    # must finish inside the kill window, or SIGKILL would skip the tool's
    # own worker reaping and orphan jax.distributed processes on the host
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "multiproc_mesh.py"),
         "--timeout", "240"],
        env=env, capture_output=True, text=True, timeout=580)


def _assert_ok(r, n_procs: int):
    ok = [ln for ln in r.stdout.splitlines()
          if ln.startswith("MULTIPROC MESH OK")]
    if "Multiprocess computations aren't implemented on the CPU backend" \
            in (r.stdout + r.stderr):
        # infrastructure, not a product failure: this jaxlib's CPU client
        # has no cross-process collectives (newer jaxlibs ship the gloo
        # backend). The path still runs wherever the suite has a capable
        # jaxlib or real chips.
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives")
    assert r.returncode == 0, (r.stdout[-800:], r.stderr[-800:])
    assert len(ok) == n_procs, r.stdout[-800:]


def test_two_process_mesh_runs_distributed_tier():
    _assert_ok(_run("2", "4"), 2)


@pytest.mark.nightly
def test_four_process_mesh_same_programs():
    """N>2 processes, same SPMD programs, same results: the 4-host x 2-chip
    geometry of the same 8-device mesh (nightly: a second full
    jax.distributed bring-up)."""
    _assert_ok(_run("4", "2"), 4)
