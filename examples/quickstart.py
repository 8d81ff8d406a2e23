"""Quickstart: the paper's portable-kernel workflow in five minutes.

    PYTHONPATH=src python examples/quickstart.py

1. Run a science kernel through the portable registry on two backends.
2. Validate the Pallas kernel against the oracle (the paper's C1).
3. Compute the performance-portability metric Phi-bar (the paper's C3).
4. Run one LM train step + one decode step on a reduced config.
"""

import jax
import jax.numpy as jnp
import numpy as np

# importing ops registers the kernels
import repro.kernels.babelstream.ops  # noqa: F401
import repro.kernels.stencil7.ops  # noqa: F401
from repro.core import Efficiency, get_kernel, phi_bar
from repro.configs import get_config
from repro.launch.compile_cache import configure_compile_cache
from repro.models import transformer as T
from repro.training.serve_step import generate
from repro.training.train_step import TrainConfig, make_train_state, train_step


def science_kernels():
    print("== 1-3. portable kernels, validation, Phi-bar ==")
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal(1 << 18), jnp.float32)
    b = jnp.asarray(rng.standard_normal(1 << 18), jnp.float32)

    triad = get_kernel("babelstream.triad")
    print("backends:", sorted(triad.backends))
    out_ref = triad(a, b, backend="xla")
    out_pal = triad(a, b, backend="pallas_interpret")
    triad.validate(a, b, backend="pallas_interpret", rtol=1e-5, atol=1e-5)
    print("triad validated; |diff| =",
          float(jnp.max(jnp.abs(out_ref - out_pal))))

    t_ref = triad.time_backend(a, b, backend="xla")
    t_pal = triad.time_backend(a, b, backend="pallas_interpret", iters=3)
    e = Efficiency("cpu-host", "triad", 1 / t_pal, 1 / t_ref)
    print(f"Eq.2 FoM (xla): {triad.figure_of_merit(t_ref, a, b)}")
    print(f"Eq.4 Phi-bar (single platform): {phi_bar([e]):.3f}")


def lm_steps():
    print("\n== 4. LM framework: one train step + generation ==")
    cfg = get_config("granite-3-8b", smoke=True)
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key)
    tcfg = TrainConfig(microbatches=2)
    state = make_train_state(params, tcfg)
    batch = {
        "tokens": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
        "targets": jax.random.randint(key, (4, 32), 0, cfg.vocab_size),
        "mask": jnp.ones((4, 32), jnp.float32),
    }
    state, metrics = jax.jit(
        lambda s, b: train_step(s, b, cfg=cfg, tcfg=tcfg))(state, batch)
    print(f"train loss {float(metrics['loss']):.3f} "
          f"grad_norm {float(metrics['grad_norm']):.3f}")

    prompt = batch["tokens"][:2, :8]
    toks = generate(state["params"], cfg, prompt, max_new_tokens=8,
                    cache_len=64)
    print("generated token ids:", np.asarray(toks)[0].tolist())


if __name__ == "__main__":
    configure_compile_cache()
    science_kernels()
    lm_steps()
    print("\nquickstart OK")
