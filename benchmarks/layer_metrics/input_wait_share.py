"""input_wait_share.* (%, host clock): share of the measured window the
loop spent blocked in DevicePrefetcher.__next__, waiting for the next
staged batch.
"""


def read(run):
    return 100.0 * run.wait_s / run.window_s
