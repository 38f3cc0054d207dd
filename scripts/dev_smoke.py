import jax, jax.numpy as jnp
from repro.configs.base import ModelConfig, MoEConfig, MLAConfig, SSMConfig, XLSTMConfig
from repro.models import lm, encdec, vit

key = jax.random.PRNGKey(0)


def check_lm(cfg, S=64, Bsz=2):
    p = lm.init_lm(key, cfg)
    tok = jax.random.randint(key, (Bsz, S), 0, cfg.vocab_size)
    batch = {"tokens": tok, "labels": tok}
    loss, met = jax.jit(lambda p, b: lm.lm_loss(p, b, cfg))(p, batch)
    assert jnp.isfinite(loss), (cfg.arch_id, loss)
    # staged loss (LW stage 2 of reduced model)
    loss2, _ = jax.jit(lambda p, b: lm.lm_loss(p, b, cfg, sub_layers=1, active_from=0))(p, batch)
    assert jnp.isfinite(loss2)
    # decode
    caches = lm.init_caches(cfg, Bsz, 32)
    logits, caches = jax.jit(lambda p, c, t: lm.decode_step(p, c, t, jnp.int32(0), cfg))(p, caches, tok[:, :1])
    assert logits.shape == (Bsz, 1, cfg.vocab_size) and jnp.isfinite(logits).all()
    # prefill
    lg, _ = jax.jit(lambda p, t: lm.prefill(p, t, cfg))(p, tok)
    assert jnp.isfinite(lg).all()
    print("OK", cfg.arch_id, float(loss))


dense = ModelConfig("t-dense", "dense", 2, 128, 4, 2, 256, 128, compute_dtype="float32")
check_lm(dense)

moe = ModelConfig("t-moe", "moe", 2, 128, 4, 2, 0, 128, compute_dtype="float32",
                  moe=MoEConfig(4, 2, 1, 128))
check_lm(moe)

mla = ModelConfig("t-mla", "moe", 2, 128, 4, 4, 0, 128, compute_dtype="float32",
                  moe=MoEConfig(4, 2, 1, 128),
                  mla=MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16))
check_lm(mla)

ssm = ModelConfig("t-mamba", "ssm", 2, 128, 4, 4, 0, 128, compute_dtype="float32",
                  ssm=SSMConfig(state_dim=16, head_dim=32, chunk_size=16))
check_lm(ssm)

xl = ModelConfig("t-xlstm", "ssm", 4, 128, 4, 4, 0, 128, compute_dtype="float32",
                 xlstm=XLSTMConfig(slstm_every=2))
check_lm(xl)

zam = ModelConfig("t-zamba", "hybrid", 4, 128, 4, 2, 256, 128, compute_dtype="float32",
                  attn_every=2, ssm=SSMConfig(state_dim=16, head_dim=32, chunk_size=16))
check_lm(zam)

wind = ModelConfig("t-window", "dense", 2, 128, 4, 2, 256, 128, compute_dtype="float32", window=16)
check_lm(wind)

# enc-dec
ed = ModelConfig("t-encdec", "audio", 2, 128, 4, 4, 256, 128, compute_dtype="float32",
                 dec_layers=2, cross_attention=True, frontend_embed_len=8)
p = encdec.init_encdec(key, ed)
frames = jax.random.normal(key, (2, 8, 128))
tok = jax.random.randint(key, (2, 16), 0, ed.vocab_size)
loss, _ = jax.jit(lambda p, f, t: encdec.encdec_loss(p, {"frontend": f, "tokens": t, "labels": t}, ed))(p, frames, tok)
assert jnp.isfinite(loss)
caches = encdec.init_dec_caches(ed, 2, 16)
lg, caches = jax.jit(lambda p, c, t, m: encdec.decode_step(p, c, t, jnp.int32(0), m, ed))(p, caches, tok[:, :1], frames)
assert jnp.isfinite(lg).all()
print("OK encdec", float(loss))

# vit
vt = ModelConfig("t-vit", "dense", 2, 128, 4, 4, 256, 0, causal=False, compute_dtype="float32", act="gelu")
pv = vit.init_vit(key, vt)
imgs = jax.random.normal(key, (2, 32, 32, 3))
rep = jax.jit(lambda p, x: vit.vit_suffix(p, vit.vit_prefix(p, x, vt), vt))(pv, imgs)
assert rep.shape == (2, 128) and jnp.isfinite(rep).all()
rep2 = jax.jit(lambda p, x: vit.vit_suffix(p, vit.vit_prefix(p, x, vt), vt, sub_layers=1))(pv, imgs)
assert jnp.isfinite(rep2).all()
print("OK vit")
print("ALL MODELS OK")
