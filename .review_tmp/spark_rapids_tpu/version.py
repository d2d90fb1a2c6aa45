"""Build/version stamping (reference: build/build-info generates
version-info.properties into the jar — pom.xml:467-492; read back via
`ai.rapids.cudf.NativeDepsLoader` consumers). Exposes the same fields:
version, user, revision, branch, date, url."""
from __future__ import annotations

import functools
import os
import subprocess

__version__ = "0.1.0"


def _git(*args: str) -> str:
    try:
        out = subprocess.run(["git", *args], capture_output=True, text=True,
                             cwd=os.path.dirname(os.path.dirname(__file__)),
                             timeout=5)
        return out.stdout.strip() if out.returncode == 0 else ""
    except Exception:
        return ""


@functools.lru_cache(None)
def version_info() -> dict:
    """The version-info.properties equivalent, computed once per process."""
    import datetime
    return {
        "version": __version__,
        "user": os.environ.get("USER", ""),
        "revision": _git("rev-parse", "HEAD"),
        "branch": _git("rev-parse", "--abbrev-ref", "HEAD"),
        "date": datetime.datetime.now(datetime.timezone.utc).isoformat(),
        "url": _git("config", "--get", "remote.origin.url"),
    }
