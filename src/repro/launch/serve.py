"""Batched serving driver (reduced-scale on CPU):

  python -m repro.launch.serve --arch qwen3-4b --reduced --batch 4 \
      --prompt-len 32 --new-tokens 16
"""
from __future__ import annotations

import argparse

import numpy as np


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    from repro.configs import get_config, get_reduced_config
    from repro.models import batch_extras, build_model
    from repro.models.common import init_params
    from repro.serve.decode import ServeConfig, ServingLoop

    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    model = build_model(cfg, max_cache_len=args.prompt_len + args.new_tokens)
    params = init_params(model.param_specs(), jax.random.PRNGKey(0))

    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab_size,
                           (args.batch, args.prompt_len)).astype(np.int32)
    loop = ServingLoop(model, params, args.batch, args.prompt_len,
                       ServeConfig(max_new_tokens=args.new_tokens,
                                   temperature=args.temperature))
    # modality stubs ride along via the prefill batch
    extras = batch_extras(cfg, args.batch)
    if extras:
        import jax.numpy as jnp
        batch = {"tokens": jnp.asarray(prompts), **extras}
        from repro.serve.decode import generate
        out = generate(model, params, batch, loop.cfg)
    else:
        out = loop.serve(prompts)
    print(f"arch={cfg.name} generated {out.shape} tokens:")
    print(out[:, :12])
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
