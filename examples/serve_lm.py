"""Batched serving example: prefill + decode with KV cache for a dense
GQA model and an attention-free SSM, reporting tokens/s.

    PYTHONPATH=src python examples/serve_lm.py
"""

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.decode_demo import serve


def main() -> None:
    enable_compile_cache()
    for arch in ("qwen3-4b", "mamba2-130m"):
        r = serve(arch, smoke=True, batch=4, prompt_len=64, gen_tokens=24)
        print(
            f"{arch:<16} prefill {r['prefill_s']*1e3:8.1f} ms   "
            f"decode {r['decode_tok_per_s']:8.1f} tok/s   "
            f"sample: {r['generated'][0][:8].tolist()}"
        )


if __name__ == "__main__":
    main()
