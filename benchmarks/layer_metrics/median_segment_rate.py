"""median_segment_rate.* (samples/s/chip, host clock): samples a segment
over the MEDIAN segment time, per chip: the rate the loop holds between
stalls.  A stall that hits a few segments does not move it and does move
the end-to-end rate, which is all the samples over all the window; the
distance between the two is what the run's stalls cost.
"""


def read(run):
    return run.median_rate
