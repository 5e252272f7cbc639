"""build_s (s, host clock): seconds in the model's constructor (flax init,
optimizer state, replication over the mesh); part of setup_s.
"""


def read(run):
    return run.phases["build_s"]
