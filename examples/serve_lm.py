"""Serving example: batched greedy/temperature generation over every cache
family — full KV (granite), SWA rolling buffer (mixtral), recurrent state
(xlstm), encoder-decoder (whisper).

    PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import numpy as np

from repro import configs
from repro.models import api
from repro.serving.serve_loop import BatchServer, GenConfig, Generator


def decoder_demo(name, max_new=8):
    cfg = configs.tiny(configs.get(name))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    server = BatchServer(cfg, params, batch_size=4,
                         gen=GenConfig(max_new_tokens=max_new))
    for _ in range(6):
        server.submit(rng.integers(0, cfg.vocab, int(rng.integers(4, 12))),
                      max_new)
    t0 = time.perf_counter()
    done = server.run_until_drained()
    dt = time.perf_counter() - t0
    n = sum(len(r.result) for r in done.values())
    print(f"{name:20s} ({cfg.family}): {len(done)} reqs, {n} tokens, "
          f"{n / dt:6.1f} tok/s")


def whisper_demo(max_new=8):
    cfg = configs.tiny(configs.get("whisper-small"))
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    gen = Generator(cfg, params, GenConfig(max_new_tokens=max_new))
    prompts = rng.integers(0, cfg.vocab, (2, 4)).astype(np.int32)
    frames = rng.standard_normal((2, 16, cfg.d_model)).astype(np.float32)
    t0 = time.perf_counter()
    out = gen.generate(prompts, frame_embeds=frames)
    dt = time.perf_counter() - t0
    print(f"{'whisper-small':20s} (audio): transcribed 2 streams → "
          f"{out.shape} in {dt:.1f}s")


def main():
    for name in ("granite-8b", "mixtral-8x7b", "xlstm-1.3b",
                 "recurrentgemma-9b"):
        decoder_demo(name)
    whisper_demo()
    print("OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
