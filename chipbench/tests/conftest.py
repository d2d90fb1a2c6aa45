"""The four-chip cell's rehearsal needs four devices: where nothing has
chosen a device count yet, give the CPU backend four (a one-chip cell
takes the first)."""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=4").strip()
