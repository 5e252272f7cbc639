"""theanompi_tpu — a TPU-native distributed training framework.

A from-scratch rebuild of the capabilities of Theano-MPI
(saadmahboob/Theano-MPI; arXiv:1605.08325): data-parallel CNN training
under four parallel rules — synchronous BSP plus asynchronous EASGD,
ASGD and GOSGD — over a model zoo (Cifar10 CNN, AlexNet, GoogLeNet,
VGG16, ResNet-50, Wasserstein GAN), a parallel ImageNet input pipeline,
per-epoch checkpoint/resume, a calc/comm/wait recorder, and
``tmlauncher``/``tmlocal`` entry points.

It is NOT a port.  Where the reference ran one OS process per GPU with
explicit mpi4py/NCCL exchangers (reference layout:
``theanompi/lib/exchanger.py``, ``theanompi/lib/base.py`` — see
SURVEY.md §1–§2; the reference mount was empty so no file:line cites
are possible), this framework is idiomatic JAX/XLA:

* BSP gradient exchange is ``jax.lax.psum`` over a named ``data`` mesh
  axis inside a single jitted SPMD step (ICI collectives scheduled by
  XLA), not a post-step MPI/NCCL call.
* The async rules (EASGD/ASGD/GOSGD) keep their process/actor topology,
  but parameter traffic rides XLA host<->device transfers and (multi-
  host) DCN instead of GPUDirect/mpi4py.
* No CUDA, no mpi4py anywhere in the build.

Public API parity surface (reference ``theanompi/__init__.py``):

    from theanompi_tpu import BSP
    rule = BSP()
    rule.init(devices=..., modelfile='theanompi_tpu.models.cifar10',
              modelclass='Cifar10_model')
    rule.wait()
"""

__version__ = "0.1.0"

__all__ = ["BSP", "EASGD", "ASGD", "GOSGD", "__version__"]

_RULES = ("BSP", "EASGD", "ASGD", "GOSGD")


def __getattr__(name):
    # Lazy so that `import theanompi_tpu.parallel` doesn't pull in the
    # whole rule/model stack (and so partial builds stay importable).
    if name in _RULES:
        try:
            import theanompi_tpu.rules as _rules
        except ImportError as e:
            raise AttributeError(
                f"rule {name!r} is unavailable: theanompi_tpu.rules failed "
                f"to import ({e})"
            ) from e
        return getattr(_rules, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
