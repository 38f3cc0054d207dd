"""Plain reference of federated MoCo v3 rounds on a ViT encoder.

Written from the method's description (LW-FedSSL, arXiv:2401.11647,
Algorithms 1 and 2; MoCo v3, arXiv:2104.02057) in straightforward
``jax.numpy``. It imports nothing of the program and takes nothing the
program made: it builds its own weights from the run key, draws its own
cohort, batches and augmentations from the same key chain, and trains one
client at a time. Every product runs at float32 ``HIGHEST`` precision.

The architecture follows the program's ViT as configured, including three
departures from the published ViT that the program makes and the
reference therefore makes too: RMSNorm in place of LayerNorm, rotary
position embeddings on queries and keys in addition to the learned
positions, and the tanh approximation of GELU.

``Numerics("control")`` is the same computation one precision below what
the configuration states: the encoder's products (stated bfloat16) take
per-tensor scaled float8 (e4m3) inputs, and the products stated float32
(patch embedding, heads, contrastive logits) take bfloat16 inputs; every
product still accumulates in float32. A comparison that cannot tell the
control from the reference cannot tell a lower-precision program either.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0                       # largest finite float8_e4m3fn value


# ---------------------------------------------------------------------------
# numerics
# ---------------------------------------------------------------------------
def _fp8(a):
    """Per-tensor scaled float8 (e4m3): (values as bfloat16, scale)."""
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), scale


@dataclass(frozen=True)
class Numerics:
    """``reference``: float32 products at HIGHEST precision; ``control``:
    one precision below the configuration's (see the module docstring).
    The control's rounded inputs are exact in bfloat16, so one bfloat16
    pass with float32 accumulation computes their products exactly."""
    mode: str = "reference"

    def __post_init__(self):
        if self.mode not in ("reference", "control"):
            raise ValueError(self.mode)

    def enc(self, spec, a, b):
        """A product the configuration computes in bfloat16."""
        if self.mode == "reference":
            return jnp.einsum(spec, a, b, precision=HIGHEST)
        (qa, sa), (qb, sb) = _fp8(a), _fp8(b)
        return jnp.einsum(spec, qa, qb,
                          preferred_element_type=jnp.float32) * (sa * sb)

    def f32(self, spec, a, b):
        """A product the configuration computes in float32."""
        if self.mode == "reference":
            return jnp.einsum(spec, a, b, precision=HIGHEST)
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# weights, drawn from the key exactly as the described initialisation says:
# truncated-normal fan-in matrices, N(0, 0.02) positions and CLS, unit norm
# scales, and for the heads BatchNorm with unit scale and zero bias
# ---------------------------------------------------------------------------
def _dense(key, shape):
    std = 1.0 / np.sqrt(shape[0])
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape)
            * std).astype(jnp.float32)


def _block_init(key, d, d_ff):
    ks = jax.random.split(key, 4)
    ka = jax.random.split(ks[0], 4)
    km = jax.random.split(ks[1], 3)
    return {"ln1": {"scale": jnp.ones((d,), jnp.float32)},
            "attn": {"wq": _dense(ka[0], (d, d)), "wk": _dense(ka[1], (d, d)),
                     "wv": _dense(ka[2], (d, d)), "wo": _dense(ka[3], (d, d))},
            "ln2": {"scale": jnp.ones((d,), jnp.float32)},
            "mlp": {"w_up": _dense(km[0], (d, d_ff)),
                    "w_down": _dense(km[1], (d_ff, d))}}


def _head_init(key, dims):
    ks = jax.random.split(key, len(dims) - 1)
    return {"layers": [{"w": _dense(ks[i], (a, b)),
                        "bn": {"scale": jnp.ones((b,), jnp.float32),
                               "bias": jnp.zeros((b,), jnp.float32)}}
                       for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))]}


def init_state(key, cfg):
    """Online encoder and heads, and the target branch as their copy."""
    m, s = cfg["model"], cfg["ssl"]
    d, L = m["d_model"], m["num_layers"]
    ke, kp, kq = jax.random.split(key, 3)
    ks = jax.random.split(ke, 4)
    n_tok = (cfg["image_size"] // cfg["patch_size"]) ** 2
    pp3 = cfg["patch_size"] ** 2 * 3
    enc = {"patch": _dense(ks[0], (pp3, d)),
           "pos": jax.random.normal(ks[1], (n_tok + 1, d)) * 0.02,
           "cls": jax.random.normal(ks[2], (1, 1, d)) * 0.02,
           "blocks": jax.vmap(lambda k: _block_init(k, d, m["d_ff"]))(
               jax.random.split(ks[3], L)),
           "final_ln": {"scale": jnp.ones((d,), jnp.float32)}}
    proj = _head_init(kp, (d, s["proj_hidden"], s["proj_hidden"],
                           s["proj_dim"]))
    pred = _head_init(kq, (s["proj_dim"], s["pred_hidden"], s["proj_dim"]))
    online = {"enc": enc, "proj": proj, "pred": pred}
    return {"online": online,
            "target": {"enc": jax.tree.map(jnp.copy, enc),
                       "proj": jax.tree.map(jnp.copy, proj)}}


# ---------------------------------------------------------------------------
# model
# ---------------------------------------------------------------------------
def _rmsnorm(scale, x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """Rotate the two halves of each head by position (x: B, S, H, hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _block(p, x, m, nm):
    B, S, d = x.shape
    H = m["num_heads"]
    hd = d // H
    h = _rmsnorm(p["ln1"]["scale"], x, m["norm_eps"])
    q, k, v = (nm.enc("bsd,de->bse", h, p["attn"][w]).reshape(B, S, H, hd)
               for w in ("wq", "wk", "wv"))
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    logits = nm.enc("bshd,bthd->bhst", q, k) / jnp.sqrt(jnp.float32(hd))
    att = nm.enc("bhst,bthd->bshd", jax.nn.softmax(logits, -1), v)
    x = x + nm.enc("bse,ed->bsd", att.reshape(B, S, d), p["attn"]["wo"])
    h = _rmsnorm(p["ln2"]["scale"], x, m["norm_eps"])
    up = jax.nn.gelu(nm.enc("bsd,df->bsf", h, p["mlp"]["w_up"]),
                     approximate=True)
    return x + nm.enc("bsf,fd->bsd", up, p["mlp"]["w_down"])


def encode(enc, images, cfg, nm, *, sub, active_from):
    """CLS representation of the first ``sub`` blocks; blocks below
    ``active_from`` pass no gradient."""
    m, P = cfg["model"], cfg["patch_size"]
    B, Hh, W, C = images.shape
    x = images.reshape(B, Hh // P, P, W // P, P, C).transpose(0, 1, 3, 2, 4, 5)
    x = nm.f32("bnp,pd->bnd", x.reshape(B, -1, P * P * C), enc["patch"])
    cls = jnp.broadcast_to(enc["cls"], (B, 1, m["d_model"]))
    x = jnp.concatenate([cls, x], axis=1) + enc["pos"][None]
    blk = jax.checkpoint(lambda xx, p: (_block(p, xx, m, nm), None))

    def run(x, lo, hi):
        if hi <= lo:
            return x
        part = jax.tree.map(lambda a: a[lo:hi], enc["blocks"])
        return jax.lax.scan(blk, x, part)[0]

    act = min(active_from, sub)
    if act > 0:
        x = jax.lax.stop_gradient(run(x, 0, act))
    x = run(x, act, sub)
    return _rmsnorm(enc["final_ln"]["scale"], x, m["norm_eps"])[:, 0]


def _head(p, x, nm):
    n = len(p["layers"])
    for i, layer in enumerate(p["layers"]):
        x = nm.f32("bi,io->bo", x, layer["w"])
        mu = jnp.mean(x, 0, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), 0, keepdims=True)
        x = ((x - mu) * jax.lax.rsqrt(var + 1e-5) * layer["bn"]["scale"]
             + layer["bn"]["bias"])
        if i < n - 1:
            x = jax.nn.relu(x)
    return x


def _info_nce(q, k, tau, nm):
    """In-batch InfoNCE: row i's positive is row i of ``k``."""
    def unit(x):
        return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True),
                               1e-12)
    logits = nm.f32("bd,cd->bc", unit(q), unit(k)) / tau
    return jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.diagonal(logits))


def ssl_loss(online, target, x1, x2, cfg, nm, *, sub, active_from,
             global_enc=None, align_weight=0.0):
    """Symmetric MoCo v3 loss, plus the representation-alignment loss
    (LW-FedSSL Eq. 3) against the frozen global encoder when asked."""
    tau = cfg["ssl"]["temperature"]
    sg = jax.lax.stop_gradient

    def online_branch(x):
        z = encode(online["enc"], x, cfg, nm, sub=sub,
                   active_from=active_from)
        return z, _head(online["pred"], _head(online["proj"], z, nm), nm)

    def target_branch(x):
        z = encode(target["enc"], x, cfg, nm, sub=sub, active_from=sub)
        return sg(_head(target["proj"], z, nm))

    z1, q1 = online_branch(x1)
    z2, q2 = online_branch(x2)
    k1, k2 = target_branch(x1), target_branch(x2)
    loss = _info_nce(q1, k2, tau, nm) + _info_nce(q2, k1, tau, nm)
    if align_weight > 0.0:
        zg1 = sg(encode(global_enc, x1, cfg, nm, sub=sub, active_from=0))
        zg2 = sg(encode(global_enc, x2, cfg, nm, sub=sub, active_from=0))
        loss = loss + align_weight * (_info_nce(z1, zg2, tau, nm)
                                      + _info_nce(z2, zg1, tau, nm))
    return loss


# ---------------------------------------------------------------------------
# augmentation: the MoCo v3 recipe (random resized crop, colour jitter,
# grayscale, flip, blur, solarisation), drawn per image from the step key
# ---------------------------------------------------------------------------
def _crop(key, img, scale=(0.2, 1.0)):
    H, W, _ = img.shape
    k1, k2, k3 = jax.random.split(key, 3)
    side = jnp.sqrt(jax.random.uniform(k1, (), minval=scale[0],
                                       maxval=scale[1]))
    ch = jnp.maximum(1, (side * H).astype(jnp.int32))
    cw = jnp.maximum(1, (side * W).astype(jnp.int32))
    y0 = jax.random.randint(k2, (), 0, H) % jnp.maximum(1, H - ch + 1)
    x0 = jax.random.randint(k3, (), 0, W) % jnp.maximum(1, W - cw + 1)
    ys = y0 + (jnp.arange(H) + 0.5) / H * ch - 0.5
    xs = x0 + (jnp.arange(W) + 0.5) / W * cw - 0.5
    y_lo = jnp.clip(jnp.floor(ys).astype(jnp.int32), 0, H - 1)
    x_lo = jnp.clip(jnp.floor(xs).astype(jnp.int32), 0, W - 1)
    y_hi, x_hi = jnp.clip(y_lo + 1, 0, H - 1), jnp.clip(x_lo + 1, 0, W - 1)
    wy, wx = (ys - y_lo)[:, None, None], (xs - x_lo)[None, :, None]

    def g(yy, xx):
        return img[yy][:, xx]
    return (g(y_lo, x_lo) * (1 - wy) * (1 - wx) + g(y_lo, x_hi) * (1 - wy) * wx
            + g(y_hi, x_lo) * wy * (1 - wx) + g(y_hi, x_hi) * wy * wx)


def _jitter(key, img, strength=0.4):
    kb, kc, ks, kh = jax.random.split(key, 4)

    def u(k):
        return 1.0 + jax.random.uniform(k, (), minval=-strength,
                                        maxval=strength)
    img = img * u(kb)
    mean = jnp.mean(img, axis=(0, 1), keepdims=True)
    img = (img - mean) * u(kc) + mean
    gray = jnp.mean(img, axis=-1, keepdims=True)
    img = gray + (img - gray) * u(ks)
    h = jnp.abs(jax.random.uniform(kh, (), minval=-0.1, maxval=0.1))
    return jnp.clip(img * (1 - h) + jnp.roll(img, 1, axis=-1) * h, 0.0, 1.0)


def _blur(key, img, p=0.5, ksize=5):
    k1, k2 = jax.random.split(key)
    sigma = jax.random.uniform(k1, (), minval=0.1, maxval=2.0)
    r = ksize // 2
    w = jnp.exp(-0.5 * (jnp.arange(-r, r + 1, dtype=jnp.float32) / sigma) ** 2)
    w = w / jnp.sum(w)
    v = jnp.pad(img, [(r, r), (0, 0), (0, 0)], mode="edge")
    v = sum(v[i:i + img.shape[0]] * w[i] for i in range(ksize))
    hz = jnp.pad(v, [(0, 0), (r, r), (0, 0)], mode="edge")
    hz = sum(hz[:, i:i + img.shape[1]] * w[i] for i in range(ksize))
    return jnp.where(jax.random.uniform(k2) < p, hz, img)


def _augment(key, img):
    ks = jax.random.split(key, 6)
    img = _jitter(ks[1], _crop(ks[0], img))
    gray = jnp.broadcast_to(jnp.mean(img, -1, keepdims=True), img.shape)
    img = jnp.where(jax.random.uniform(ks[2]) < 0.2, gray, img)
    img = jnp.where(jax.random.uniform(ks[3]) < 0.5, img[:, ::-1], img)
    img = _blur(ks[4], img)
    sol = jnp.where(img >= 0.5, 1.0 - img, img)
    return jnp.where(jax.random.uniform(ks[5]) < 0.2, sol, img)


def two_views(key, images):
    k1, k2 = jax.random.split(key)
    B = images.shape[0]
    return (jax.vmap(_augment)(jax.random.split(k1, B), images),
            jax.vmap(_augment)(jax.random.split(k2, B), images))


# ---------------------------------------------------------------------------
# optimisation: AdamW with decoupled weight decay, an update mask for the
# frozen part of the model, and the target branch's EMA
# ---------------------------------------------------------------------------
def _path(path):
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def update_mask(online, sub, active_from):
    """1 where a stage-``sub`` model trains, 0 where it is frozen: block
    rows [active_from, sub), the patch/position/CLS embedding only when
    the whole prefix trains, the final norm and the heads always."""
    def leaf(path, a):
        keys = _path(path)
        if "blocks" in keys:
            rows = jnp.arange(a.shape[0])
            m = ((rows >= active_from) & (rows < sub)).astype(jnp.float32)
            return m.reshape((-1,) + (1,) * (a.ndim - 1))
        if keys[-1] in ("patch", "pos", "cls"):
            return jnp.float32(1.0 if active_from == 0 else 0.0)
        return jnp.float32(1.0)
    return jax.tree_util.tree_map_with_path(leaf, online)


def adamw_init(params):
    z = jax.tree.map(jnp.zeros_like, params)
    return {"mu": z, "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def adamw(grads, opt, params, lr, mask, tc):
    b1, b2, eps, wd = tc["b1"], tc["b2"], tc["eps"], tc["weight_decay"]
    c = opt["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, opt["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, opt["nu"], grads)
    bc1 = 1 - b1 ** c.astype(jnp.float32)
    bc2 = 1 - b2 ** c.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m, v, k: p - k * lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                         + wd * p),
        params, mu, nu, mask)
    return new, {"mu": mu, "nu": nu, "count": c}


def _norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(a * a)), tree)


def ema(target, online, mu):
    return jax.tree.map(lambda t, o: mu * t + (1.0 - mu) * o, target,
                        {"enc": online["enc"], "proj": online["proj"]})


# ---------------------------------------------------------------------------
# one round
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Plan:
    """What a round of a cell does (the method's stage plan; every round
    of a cell is at one stage)."""
    sub: int                 # blocks in the model this round
    active_from: int         # blocks below this are frozen on clients
    align: bool              # clients add the alignment loss
    calibrate: bool          # the server trains on its auxiliary set
    upload: tuple            # block rows clients send back [lo, hi)
    transfer: bool           # round 0: block sub-1 starts as a copy of
                             # block sub-2


def round_plan(traffic, num_layers):
    if traffic["schedule"] == "e2e":
        L = num_layers
        return Plan(L, 0, False, False, (0, L), False)
    if traffic["schedule"] == "lw_fedssl":
        s = traffic["stage"]
        return Plan(s, s - 1, True, True, (s - 1, s), s >= 2)
    raise ValueError(f"no reference for schedule {traffic['schedule']!r}")


def lr_at(round_idx, traffic, tc):
    """Cosine decay over the traffic's rounds from base_lr * batch / 256."""
    base = tc["base_lr"] * traffic["batch"] / 256.0
    if tc["lr_schedule"] != "cosine" or tc.get("warmup_steps", 0):
        raise ValueError("the reference implements cosine decay, no warmup")
    t = jnp.clip(jnp.float32(round_idx) / max(1.0, float(traffic["rounds"])),
                 0.0, 1.0)
    return float(jnp.float32(base) * 0.5 * (1.0 + jnp.cos(jnp.pi * t)))


class RoundRef:
    """Compiled pieces of the reference rounds of one cell and numerics."""

    def __init__(self, cfg, traffic, numerics: Numerics):
        self.cfg, self.traffic, self.nm = cfg, traffic, numerics
        self.tc = cfg["train"]
        self.plan = round_plan(traffic, cfg["model"]["num_layers"])
        self.client_step = jax.jit(self._client_step)
        self.calib_step = jax.jit(self._calib_step)

    def _client_step(self, st, opt, images, key, lr, global_enc):
        p, s = self.plan, self.cfg["ssl"]
        k_aug, _ = jax.random.split(key)
        x1, x2 = two_views(k_aug, images)
        loss, grads = jax.value_and_grad(
            lambda o: ssl_loss(o, st["target"], x1, x2, self.cfg, self.nm,
                               sub=p.sub, active_from=p.active_from,
                               global_enc=global_enc,
                               align_weight=(s["align_weight"] if p.align
                                             else 0.0)))(st["online"])
        online, opt = adamw(grads, opt, st["online"], lr,
                            update_mask(st["online"], p.sub, p.active_from),
                            self.tc)
        return ({"online": online,
                 "target": ema(st["target"], online, s["momentum"])},
                opt, loss, _norms(grads))

    def _calib_step(self, st, opt, images, key, lr):
        p, s = self.plan, self.cfg["ssl"]
        x1, x2 = two_views(key, images)
        loss, grads = jax.value_and_grad(
            lambda o: ssl_loss(o, st["target"], x1, x2, self.cfg, self.nm,
                               sub=p.sub, active_from=0))(st["online"])
        online, opt = adamw(grads, opt, st["online"], lr,
                            update_mask(st["online"], p.sub, 0), self.tc)
        return ({"online": online,
                 "target": ema(st["target"], online, s["momentum"])}, opt,
                _norms(grads))

    def _local_train(self, online, images, key, lr, global_enc):
        """Algorithm 2: local epochs over a shuffled shard, the target
        branch restarted from the downloaded model; returns the trained
        online model, the last step's loss and the first step's gradient
        norms."""
        st = {"online": online,
              "target": {"enc": online["enc"], "proj": online["proj"]}}
        opt = adamw_init(online)
        n, bs = images.shape[0], self.traffic["batch"]
        loss, first = None, None
        for _ in range(self.traffic["local_epochs"]):
            key, kp = jax.random.split(key)
            perm = jax.random.permutation(kp, n)
            for b in range(n // bs):
                key, kb = jax.random.split(key)
                st, opt, loss, g = self.client_step(
                    st, opt, images[perm[b * bs:(b + 1) * bs]], kb,
                    jnp.float32(lr), global_enc)
                first = g if first is None else first
        return st["online"], float(loss), first

    def _calibrate(self, state, aux, key, lr):
        """Algorithm 1 line 7: server epochs over the auxiliary set with
        a fresh optimizer; returns the state and the first step's gradient
        norms."""
        opt = adamw_init(state["online"])
        first = None
        n, bs = aux.shape[0], min(self.traffic["batch"], aux.shape[0])
        for _ in range(self.traffic["server_epochs"]):
            key, kp = jax.random.split(key)
            perm = jax.random.permutation(kp, n)
            for b in range(n // bs):
                key, kb = jax.random.split(key)
                state, opt, g = self.calib_step(
                    state, opt, aux[perm[b * bs:(b + 1) * bs]], kb,
                    jnp.float32(lr))
                first = g if first is None else first
        return state, first

    def _fedavg(self, server, trained, weights):
        """Weighted mean of what clients send back (block rows
        ``plan.upload``, the final norm, the heads, and the embedding when
        the prefix trained); everything else keeps the server's value."""
        lo, hi = self.plan.upload
        send_embed = self.plan.active_from == 0

        def leaf(path, a, *cs):
            keys = _path(path)
            mean = sum(w * c for w, c in zip(weights, cs))
            if "blocks" in keys:
                return a.at[lo:hi].set(mean[lo:hi])
            if keys[-1] in ("patch", "pos", "cls") and not send_embed:
                return a
            return mean
        return jax.tree_util.tree_map_with_path(leaf, server, *trained)

    def _round(self, state, key, round_idx, pool, shards, aux):
        """One round from server state ``state``: returns (state after
        the round, the key after it, mean last-step client loss, per-leaf
        norm of the first gradient each part of the model gets: the larger
        of the first client step's and the first calibration step's)."""
        tr, p = self.traffic, self.plan
        lr = lr_at(round_idx, tr, self.tc)
        key, ks = jax.random.split(key)
        if tr["cohort"] >= tr["clients"]:
            cohort = list(range(tr["clients"]))
        else:
            cohort = [int(i) for i in jax.random.choice(
                ks, tr["clients"], (tr["cohort"],), replace=False)]
        client_keys = []
        for _ in cohort:
            key, kc = jax.random.split(key)
            client_keys.append(kc)
        global_enc = state["online"]["enc"] if p.align else None
        trained, losses, grads = [], [], None
        for i, kc in zip(cohort, client_keys):
            online_i, loss_i, g = self._local_train(
                state["online"], pool[jnp.asarray(shards[i])], kc, lr,
                global_enc)
            trained.append(online_i)
            losses.append(loss_i)
            grads = g if grads is None else grads
        counts = np.asarray([len(shards[i]) for i in cohort], np.float64)
        weights = [jnp.float32(c) for c in counts / counts.sum()]
        state = {**state, "online": jax.jit(self._fedavg)(
            state["online"], trained, weights)}
        del trained
        if p.calibrate:
            key, kg = jax.random.split(key)
            state, g = self._calibrate(state, aux, kg, lr)
            grads = jax.tree.map(jnp.maximum, grads, g)
        return state, key, sum(losses) / len(losses), grads

    def rounds(self, key, pool, shards, aux, n):
        """Rounds 0 to n-1 of the run keyed ``key``: returns (initial
        online model, [(online model after round i, its mean last-step
        client loss, its first-gradient norms) for each round])."""
        p = self.plan
        k_init, key = jax.random.split(key)
        state = init_state(k_init, self.cfg)
        init_online = state["online"]
        if p.transfer:
            def move(a):
                return a.at[p.sub - 1].set(a[p.sub - 2])
            state = {"online": {**state["online"], "enc": {
                         **state["online"]["enc"],
                         "blocks": jax.tree.map(
                             move, state["online"]["enc"]["blocks"])}},
                     "target": {**state["target"], "enc": {
                         **state["target"]["enc"],
                         "blocks": jax.tree.map(
                             move, state["target"]["enc"]["blocks"])}}}
        out = []
        for r in range(n):
            state, key, loss, grads = self._round(state, key, r, pool,
                                                  shards, aux)
            out.append((state["online"], loss, grads))
        return init_online, out
