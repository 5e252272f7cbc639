"""smallthinker_global_attention_roofline_share (%, device trace): the
same reading as ``smallthinker_window_attention_roofline_share`` (its
file says how) of the global layer's calls, NAMED
``smallthinker_global_attention_{fwd,bwd_kv,bwd_q}``: causal attention
with no position signal, the products over the s (s + 1) / 2 pairs of
the causal mask a head.
"""

from benchmarks.layer_metrics import (
    smallthinker_window_attention_roofline_share as window_reader)


def read(run):
    return window_reader.share(run, "global")
