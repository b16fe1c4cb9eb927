"""How the program builds a GPT-2 from a configuration's file, and what it
calls the leaves that ``reference/gpt2.py`` names. The program side of the
family: everything here is the system under test or a name."""
from __future__ import annotations


def build_model(values: dict):
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    drops = {values[k] for k in ("resid_pdrop", "embd_pdrop", "attn_pdrop")}
    if len(drops) != 1:
        raise ValueError("models/gpt.py has one dropout rate; the file "
                         f"states {sorted(drops)}")
    if values["activation_function"] != "gelu":
        raise ValueError("models/gpt.py computes the exact (erf) GELU; the "
                         "file has to state activation_function 'gelu'")
    h = values["n_embd"]
    cfg = GPTConfig(vocab_size=values["padded_vocab_size"],
                    max_position_embeddings=values["n_positions"],
                    hidden_size=h, num_layers=values["n_layer"],
                    num_heads=values["n_head"],
                    intermediate_size=values.get("n_inner") or 4 * h,
                    dropout=drops.pop(),
                    layer_norm_eps=values["layer_norm_epsilon"])
    return GPTForCausalLM(cfg)


_BLOCK = {"ln_1": "norm1", "ln_2": "norm2", "attn.q": "self_attn.q_proj",
          "attn.k": "self_attn.k_proj", "attn.v": "self_attn.v_proj",
          "attn.o": "self_attn.out_proj", "mlp.fc": "linear1",
          "mlp.proj": "linear2"}


def program_name(ref_name: str) -> str:
    if ref_name in ("wte", "wpe"):
        return f"gpt.{ref_name}.weight"
    if ref_name.startswith("ln_f."):
        return "gpt." + ref_name
    _, i, rest = ref_name.split(".", 2)
    mod, leaf = rest.rsplit(".", 1)
    return f"gpt.encoder.layers.{i}.{_BLOCK[mod]}.{leaf}"
