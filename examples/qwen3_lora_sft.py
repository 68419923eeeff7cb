"""LoRA SFT on self-cognition data — single-device fine-tune.

TPU-native counterpart of the reference's ``Fine-Tuning/qwen3-8b-lora.py``:
self-cognition records with ``{{NAME}}``/``{{AUTHOR}}`` substitution, ChatML
rendering with label masking to the assistant span, LoRA (r/alpha/targets)
on the attention projections, adapter-only optimization, adapter-only save,
then the behavioral acceptance check — ask "Who are you?" and expect the
substituted identity (``Fine-Tuning/README.md:107-119``, driven by
``Fine-Tuning/inferences.py:69-86``).

Runs on a small in-tree Qwen3 by default; pass ``--model_dir`` to fine-tune
real HF safetensors weights (``llm_in_practise_tpu.models.hf_loader``).
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from llm_in_practise_tpu.ckpt import checkpoint as ckpt
from llm_in_practise_tpu.data import BPETokenizer, build_sft_dataset
from llm_in_practise_tpu.data.sft import (
    IGNORE_INDEX,
    IM_END,
    IM_START,
    render_chatml,
    self_cognition_records,
    substitute_placeholders,
    to_chat_messages,
)
from llm_in_practise_tpu.infer.generate import generate
from llm_in_practise_tpu.models import Qwen3, qwen3_config
from llm_in_practise_tpu.peft import (
    LoRAConfig,
    apply_lora,
    init_lora,
    trainable_report,
)


def train_tokenizer(records, name, author):
    """Train a ChatML-aware BPE on the rendered SFT texts (the reference uses
    the pretrained Qwen3 tokenizer; in-tree BPE keeps this hermetic)."""
    system = f"You are a helpful assistant named {name}, trained by {author}."
    texts = [
        render_chatml(to_chat_messages(r, system))
        for r in substitute_placeholders(records, name, author)
    ]
    return BPETokenizer.train(
        texts, vocab_size=800,
        special_tokens=("[PAD]", "[UNK]", IM_START, IM_END),
        min_frequency=1,
    )


def build_tokenizer(records, name, author, path):
    """:func:`train_tokenizer`, cached at ``path``."""
    if os.path.exists(path):
        return BPETokenizer.load(path)
    tok = train_tokenizer(records, name, author)
    tok.save(path)
    return tok


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--model_dir", default=None,
                   help="HF Qwen3 checkpoint dir (safetensors); default: tiny in-tree model")
    p.add_argument("--name", default="MyBot")
    p.add_argument("--author", default="MyTeam")
    p.add_argument("--r", type=int, default=16)
    p.add_argument("--alpha", type=float, default=32.0)
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--batch_size", type=int, default=8)
    p.add_argument("--max_length", type=int, default=128)
    p.add_argument("--gradient-checkpointing",
                   dest="gradient_checkpointing", action="store_true",
                   help="remat transformer blocks in backward (reference gradient_checkpointing_enable parity)")
    p.add_argument("--adapter_dir", default="/tmp/qwen3_lora_adapter")
    p.add_argument("--tokenizer_path", default="/tmp/qwen3_sft_bpe.json")
    args = p.parse_args()

    records = self_cognition_records(n=64)
    if args.model_dir:
        # real checkpoint: its own tokenizer (AutoTokenizer parity) + weights
        from llm_in_practise_tpu.data import HFTokenizerAdapter
        from llm_in_practise_tpu.models import hf_loader

        tok = HFTokenizerAdapter.from_pretrained(args.model_dir)
        cfg = hf_loader.load_config(args.model_dir).replace(
            remat=args.gradient_checkpointing)
        model = Qwen3(cfg)
        params = hf_loader.load_qwen3(args.model_dir)[1]
    else:
        tok = build_tokenizer(records, args.name, args.author,
                              args.tokenizer_path)
        cfg = qwen3_config(tok.vocab_size, max_seq_len=args.max_length,
                           compute_dtype="float32",
                           remat=args.gradient_checkpointing)
        model = Qwen3(cfg)
        params = model.init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32),
            deterministic=True,
        )["params"]

    batch = build_sft_dataset(records, tok, name=args.name,
                              author=args.author, max_length=args.max_length)
    print(f"sft batch: {batch.input_ids.shape}, "
          f"{int((batch.labels != IGNORE_INDEX).sum())} assistant tokens")

    lcfg = LoRAConfig(r=args.r, alpha=args.alpha,
                      target_patterns=(r"attn/(q_proj|k_proj|v_proj|o_proj)",))
    lora_params = init_lora(params, lcfg, jax.random.PRNGKey(1))
    print(trainable_report(params, lora_params))

    x = jnp.asarray(batch.input_ids)
    labels = jnp.asarray(batch.labels)

    def loss_fn(lp, idx):
        logits = model.apply(
            {"params": apply_lora(params, lp, lcfg)}, x[idx],
            deterministic=True,
        )
        lab = labels[idx]
        shift_logits = logits[:, :-1].astype(jnp.float32)
        shift_labels = lab[:, 1:]
        mask = shift_labels != IGNORE_INDEX
        logp = jax.nn.log_softmax(shift_logits)
        ll = jnp.take_along_axis(
            logp, jnp.maximum(shift_labels, 0)[..., None], -1
        )[..., 0]
        return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1)

    tx = optax.adamw(args.lr)
    opt_state = tx.init(lora_params)
    step_fn = jax.jit(jax.value_and_grad(loss_fn))
    rng = np.random.default_rng(0)
    for step in range(args.steps):
        idx = jnp.asarray(rng.integers(0, len(x), (args.batch_size,)))
        loss, grads = step_fn(lora_params, idx)
        updates, opt_state = tx.update(grads, opt_state, lora_params)
        lora_params = optax.apply_updates(lora_params, updates)
        if step % 10 == 0 or step == args.steps - 1:
            print(f"step {step} | loss {float(loss):.4f}")

    path = ckpt.save_named(
        args.adapter_dir, lora_params, "adapter",
        metadata={"lora_config": lcfg.to_dict()},
    )
    print(f"adapter saved -> {path}")

    # Behavioral acceptance: the tuned model should answer with its identity.
    system = (f"You are a helpful assistant named {args.name}, "
              f"trained by {args.author}.")
    prompt = render_chatml([
        {"role": "system", "content": system},
        {"role": "user", "content": "Who are you?"},
    ]) + f"{IM_START}assistant\n"
    ids = jnp.asarray(tok.encode(prompt))[None, :]
    tuned = apply_lora(params, lora_params, lcfg)
    out = generate(model, tuned, ids, max_new_tokens=24, greedy=True,
                   eos_id=tok.token_to_id(IM_END))
    answer = tok.decode(np.asarray(out[0]).tolist()[ids.shape[1]:])
    print("Q: Who are you?")
    print("A:", answer.strip())


if __name__ == "__main__":
    main()
