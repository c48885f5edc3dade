"""Import a torch Llama/Mistral checkpoint and generate with the KV cache.

The migration story end to end: build a HF model (here randomly
initialized — swap in ``from_pretrained`` when you have weights), map
its state dict onto the TPU-native :class:`LlamaModel`, and sample with
the jitted KV-cache decode loop.  With ``--window`` the model uses
sliding-window attention (Mistral-style): training/prefill run the
banded flash grid and the decode cache is a window-sized ring buffer.

Run (CPU works):
    python examples/llama_generate.py [--window 8] [--temperature 0.8]
                                      [--prefill-chunk 4]

``--prefill-chunk`` demonstrates chunked prefill (the long-prompt
path: prompts above 8k tokens chunk automatically so a 32k-token
prompt compiles; forcing a small chunk here shows the output is
identical either way).
"""

from __future__ import annotations

import argparse

import jax
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--window", type=int, default=None,
                    help="sliding-window size (Mistral-style)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--max-new-tokens", type=int, default=24)
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prefill the prompt in chunks of this many "
                         "tokens (None = auto: single call below 8k)")
    args = ap.parse_args()
    from apex_tpu.utils import enable_compile_cache

    enable_compile_cache()

    import torch
    from transformers import LlamaConfig as HFLlamaConfig
    from transformers import LlamaForCausalLM

    from apex_tpu.models import (
        LlamaConfig,
        LlamaModel,
        generate,
        load_torch_llama,
    )

    # a tiny GQA llama; replace with LlamaForCausalLM.from_pretrained
    torch.manual_seed(0)
    hf = LlamaForCausalLM(HFLlamaConfig(
        vocab_size=256, hidden_size=128, intermediate_size=256,
        num_hidden_layers=4, num_attention_heads=8,
        num_key_value_heads=4, max_position_embeddings=128,
        tie_word_embeddings=False)).eval()

    cfg = LlamaConfig(
        vocab_size=256, hidden_size=128, ffn_hidden_size=256,
        num_layers=4, num_heads=8, num_kv_heads=4, max_seq_len=128,
        sliding_window=args.window)
    model = LlamaModel(cfg)

    prompt = np.random.default_rng(0).integers(0, 256, size=(2, 8))
    params = model.init(jax.random.PRNGKey(0),
                        np.asarray(prompt, np.int32))
    params = load_torch_llama(params, hf.state_dict(),
                              num_heads=cfg.num_heads,
                              num_kv_heads=cfg.num_kv_heads)

    out = generate(
        model, params, prompt, max_new_tokens=args.max_new_tokens,
        temperature=args.temperature,
        prefill_chunk=args.prefill_chunk,
        rng=jax.random.PRNGKey(1) if args.temperature > 0 else None)
    for row in np.asarray(out):
        print("prompt:", row[:8].tolist())
        print("  cont:", row[8:].tolist())

    if args.temperature == 0.0 and args.window is None:
        # greedy + full attention: cross-check against torch generate
        with torch.no_grad():
            want = hf.generate(
                torch.from_numpy(prompt), do_sample=False,
                max_new_tokens=args.max_new_tokens,
                pad_token_id=0).numpy()
        assert np.array_equal(np.asarray(out), want), "torch mismatch"
        print("greedy output token-identical to torch generate")


if __name__ == "__main__":
    main()
