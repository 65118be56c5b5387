"""Batched serving demo: one compiled decode step serves a queue of requests
in slot-masked waves (the Batched-SpMM idea applied to inference).

    PYTHONPATH=src python examples/serve_demo.py
"""
import jax

from repro import configs
from repro.models import lm
from repro.serving import ServeEngine
from repro.serving.engine import Request


def main():
    cfg = configs.get("mixtral-8x22b").reduced()   # tiny MoE with SWA
    params = lm.init_params(jax.random.key(0), cfg)
    engine = ServeEngine(params, cfg, batch=4, max_len=64, temperature=0.8)
    reqs = [Request(prompt=[1 + i, 7, 42], max_new_tokens=8 + 2 * i)
            for i in range(6)]
    engine.run(reqs)
    for i, r in enumerate(reqs):
        print(f"req{i}: prompt={r.prompt} -> out={r.out}")
    assert all(r.done for r in reqs)
    assert all(len(r.out) == r.max_new_tokens for r in reqs)
    print("all requests served (2 waves of 4 slots).")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
