"""Pod-scale serving launcher: batched prefill + decode under pjit.

The decode step is the one the decode_32k / long_500k dry-run shapes lower;
here it runs for real on whatever mesh the devices support (1 CPU in this
container, a v5e pod in production).

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b \
        --scale tiny --batch 4 --prompt-len 32 --gen-len 8
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCH_CONFIGS
from repro.launch.train import mesh_from_devices
from repro.launch import sharding as sh
from repro.models import transformer as tfm


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b", choices=sorted(ARCH_CONFIGS))
    ap.add_argument("--scale", default="tiny", choices=("tiny", "full"))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=8)
    args = ap.parse_args()

    cfg = ARCH_CONFIGS[args.arch]
    if args.scale == "tiny":
        cfg = cfg.reduced()
    mesh = mesh_from_devices()
    max_len = args.prompt_len + args.gen_len
    print(f"mesh {dict(zip(mesh.axis_names, mesh.devices.shape))} "
          f"arch={cfg.name}")

    with jax.set_mesh(mesh):
        params_struct = jax.eval_shape(
            lambda k: tfm.init_params(cfg, k), jax.random.PRNGKey(0))
        params_sh = sh.param_shardings(mesh, params_struct, fsdp=False)
        params = jax.jit(lambda k: tfm.init_params(cfg, k),
                         out_shardings=params_sh)(jax.random.PRNGKey(0))

        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0,
            cfg.vocab_size)}
        if cfg.family == "vlm":
            batch["vision_embeds"] = jax.random.normal(
                jax.random.PRNGKey(2),
                (args.batch, cfg.n_vision_tokens, cfg.d_model))
        if cfg.family == "audio":
            batch["audio_frames"] = jax.random.normal(
                jax.random.PRNGKey(3),
                (args.batch, cfg.n_audio_frames, cfg.d_model))

        t0 = time.perf_counter()
        out = jax.jit(lambda p, b: tfm.forward_seq(
            cfg, p, b, want_cache=True, max_cache_len=max_len))(params, batch)
        jax.block_until_ready(out["logits"])
        print(f"prefill: {(time.perf_counter()-t0)*1e3:.0f} ms (w/ compile)")

        step = jax.jit(lambda p, t, c, pos: tfm.decode_step(cfg, p, t, c, pos))
        cache = out["cache"]
        last = out["logits"][:, -1]
        t0 = time.perf_counter()
        toks = []
        for i in range(args.gen_len):
            nxt = jnp.argmax(last, axis=-1)
            toks.append(nxt)
            logits, cache = step(params, nxt[:, None], cache,
                                 jnp.int32(args.prompt_len + i))
            last = logits[:, 0]
        jax.block_until_ready(last)
        dt = time.perf_counter() - t0
        print(f"decode {args.gen_len} tokens: {dt*1e3:.0f} ms; ids[0]="
              f"{[int(t[0]) for t in toks]}")


if __name__ == "__main__":
    main()
