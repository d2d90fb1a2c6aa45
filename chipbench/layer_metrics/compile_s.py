"""compilation: backend_compile_duration summed over set-up; a warm
persistent cache leaves the seconds its reads took."""


def read(run):
    return run.setup_compiles[2]
