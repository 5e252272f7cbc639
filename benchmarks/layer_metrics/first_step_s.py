"""first_step_s (s, host clock): the first fenced train_iter, which traces
the step program and compiles it or loads it from the persistent cache;
part of setup_s.
"""


def read(run):
    return run.phases["first_step_s"]
