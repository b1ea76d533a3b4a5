"""Set-up before the runner starts: the interpreter, the imports of JAX
and of the program, the device found and the compile cache opened.

run.py's own span `import`, host clock, from the process's first line to
the runner's call. Part of `setup_s`: with `setup_init_s`,
`setup_check_s` and `setup_warm_s` (and a traced run's `loader_drain`)
it accounts for it. It is timed from outside the program and holds no
compile-path event of the program's own.
"""


def read(obs):
    return obs.spans.get("import")
