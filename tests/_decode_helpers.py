"""What the decode tests share (test_decode.py, test_decode_spec.py,
test_frontdoor.py): the tiny model, the oracles, and the greedy
drivers of a session.

The oracles generate greedily by iterative FULL forward over the whole
sequence so far — no cache, no kernel — through ONE ``jax.jit`` of the
forward at a fixed padded length.  (Called eagerly on a sequence one
token longer each step, every primitive of the model compiled again
for every length: nine tenths of the slowest decode tests' time.)
``flax_greedy`` runs the TRAINING module and shares no code with
``theanompi_tpu.decode``; ``windowed_greedy`` runs ``full_forward``,
which test_decode.py holds to the training module first."""

import functools
import socket

import jax
import jax.numpy as jnp
import numpy as np

from theanompi_tpu.decode import full_forward
from theanompi_tpu.models.base import ModelConfig
from theanompi_tpu.models.transformer import TransformerLM
from theanompi_tpu.serving import export_model

N_LAYERS, N_HEADS, D_MODEL, VOCAB = 2, 2, 16, 32

#: sequences are right-padded to a multiple of this, so an oracle
#: compiles once a block of lengths, not once a length
PAD = 16


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def tiny_config() -> ModelConfig:
    return ModelConfig(batch_size=4, n_epochs=1, print_freq=0,
                       compute_dtype="float32", optimizer="adamw",
                       learning_rate=1e-3, weight_decay=0.0,
                       lr_schedule="constant")


def build_tiny_lm(export_dir: str):
    """One untrained tiny TransformerLM + its f32 export (v0): the
    (model, host params, export_dir) triple a module builds on."""
    model = TransformerLM(config=tiny_config(), vocab=VOCAB, seq_len=16,
                          n_layers=N_LAYERS, d_model=D_MODEL,
                          n_heads=N_HEADS, verbose=False)
    params = jax.device_get(model.state.params)
    export_model(model, export_dir, version=0)
    return model, params, export_dir


def greedy(logits_fn, prompt, n: int) -> list[int]:
    """``n`` greedy tokens after ``prompt``; ``logits_fn`` maps (1, T)
    int32 tokens to (1, T, V) logits under a CAUSAL mask.  The sequence
    is right-padded with token 0: no real position attends a pad, so
    the logits read at the last real position are the unpadded
    sequence's."""
    cur = [int(t) for t in prompt]
    padded = -(-(len(cur) + n - 1) // PAD) * PAD
    out = []
    for _ in range(n):
        toks = np.zeros((1, padded), np.int32)
        toks[0, :len(cur)] = cur
        logits = np.asarray(logits_fn(toks))
        tok = int(np.argmax(logits[0, len(cur) - 1]))
        out.append(tok)
        cur.append(tok)
    return out


@functools.partial(jax.jit, static_argnums=0)
def _module_logits(module, params, toks):
    return module.apply({"params": params}, toks, train=False,
                        seq_axis=None)


def flax_greedy(model, params, prompt, n: int) -> list[int]:
    """The independent oracle: the TRAINING module's own forward."""
    return greedy(
        lambda toks: _module_logits(model.module, params, toks),
        prompt, n)


@functools.partial(jax.jit, static_argnums=(2,))
def _windowed_logits(params, toks, window):
    return full_forward(params, toks, N_LAYERS, N_HEADS, jnp.float32,
                        window=window)[0]


def windowed_greedy(params, prompt, n: int, window: int) -> list[int]:
    """Eviction oracle: iterative full forward under the sliding-
    window mask — what the ring cache semantically IS."""
    return greedy(
        lambda toks: _windowed_logits(params, toks, window), prompt, n)


def hot(compiles: dict) -> dict:
    """The nonzero program families — new families default to 0, so
    equality pins stay exact without enumerating every key."""
    return {k: v for k, v in compiles.items() if v}


def session_greedy(sess, prompt, n: int) -> list[int]:
    seq, logits = sess.admit(np.asarray(prompt, np.int32))
    out = [int(np.argmax(logits))]
    for _ in range(n - 1):
        lg = sess.decode([seq], np.asarray([out[-1]], np.int32))
        out.append(int(np.argmax(lg[0])))
    sess.release(seq)
    return out


def spec_greedy(sess, draft, prompt, n: int, k: int = 3) -> list[int]:
    """Speculative greedy through a (target, draft) session pair:
    propose -> verify -> commit rounds until n tokens, trimmed to n
    (the emission-trim the scheduler applies)."""
    seq, logits = sess.admit(np.asarray(prompt, np.int32))
    dseq, _ = draft.admit(np.asarray(prompt, np.int32))
    out = [int(np.argmax(logits))]
    while len(out) < n:
        pending = np.asarray([out[-1]], np.int32)
        drafts = draft.propose([dseq], pending, k)
        y, counts = sess.verify([seq], pending, drafts)
        draft.commit([dseq], counts)
        out.extend(int(t) for t in y[0, :counts[0]])
    sess.release(seq)
    draft.release(dseq)
    return out[:n]
