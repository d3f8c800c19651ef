"""Diffusion UNet (north-star config "Stable Diffusion UNet: conv +
cross-attn"; architecture per the latent-diffusion UNet, built on
paddle_tpu.nn — residual GroupNorm/SiLU conv blocks, self+cross attention
at low resolutions, sinusoidal timestep embedding, skip connections).

TPU notes: convs stay NCHW at the API (XLA retiles internally); attention
blocks flatten HxW into sequence and ride the same scaled_dot_product
/ flash path as the language models — the conv+cross-attn fusion coverage
the reference exercises via CINN lands on XLA here."""

from __future__ import annotations

import math

import jax.numpy as jnp

import paddle_tpu.nn as nn
from ..framework.core import Tensor, run_op, to_tensor

__all__ = ["UNetConfig", "UNetModel", "unet_tiny"]


class UNetConfig:
    def __init__(self, in_channels=4, out_channels=4, base_channels=128,
                 channel_mult=(1, 2, 4), num_res_blocks=2,
                 attention_levels=(1, 2), num_heads=4, context_dim=512,
                 groups=32):
        self.in_channels = in_channels
        self.out_channels = out_channels
        self.base_channels = base_channels
        self.channel_mult = tuple(channel_mult)
        self.num_res_blocks = num_res_blocks
        self.attention_levels = tuple(attention_levels)
        self.num_heads = num_heads
        self.context_dim = context_dim
        self.groups = groups


def unet_tiny(**kw):
    return UNetConfig(in_channels=3, out_channels=3, base_channels=32,
                      channel_mult=(1, 2), num_res_blocks=1,
                      attention_levels=(1,), num_heads=2, context_dim=64,
                      groups=8, **kw)


def timestep_embedding(t, dim, max_period=10000.0):
    """Sinusoidal embedding [B, dim] (DDPM convention)."""
    tt = t if isinstance(t, Tensor) else to_tensor(t)

    def fn(v):
        half = dim // 2
        freqs = jnp.exp(-math.log(max_period)
                        * jnp.arange(half, dtype=jnp.float32) / half)
        args = v.astype(jnp.float32)[:, None] * freqs[None]
        return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)

    return run_op("timestep_embedding", fn, [tt])


class ResBlock(nn.Layer):
    def __init__(self, in_c, out_c, emb_dim, groups):
        super().__init__()
        self.norm1 = nn.GroupNorm(min(groups, in_c), in_c)
        self.conv1 = nn.Conv2D(in_c, out_c, 3, padding=1)
        self.emb_proj = nn.Linear(emb_dim, out_c)
        self.norm2 = nn.GroupNorm(min(groups, out_c), out_c)
        self.conv2 = nn.Conv2D(out_c, out_c, 3, padding=1)
        self.skip = (nn.Conv2D(in_c, out_c, 1) if in_c != out_c else None)
        self.act = nn.Silu()

    def forward(self, x, emb):
        h = self.conv1(self.act(self.norm1(x)))
        e = self.emb_proj(self.act(emb))
        h = run_op("res_emb_add", lambda a, b: a + b[:, :, None, None], [h, e])
        h = self.conv2(self.act(self.norm2(h)))
        s = self.skip(x) if self.skip is not None else x
        return h + s


class AttnBlock(nn.Layer):
    """Self-attention + cross-attention over flattened spatial positions."""

    def __init__(self, channels, num_heads, context_dim, groups):
        super().__init__()
        self.norm = nn.GroupNorm(min(groups, channels), channels)
        self.self_attn = nn.MultiHeadAttention(channels, num_heads)
        self.cross_attn = nn.MultiHeadAttention(
            channels, num_heads, kdim=context_dim, vdim=context_dim)
        self.norm2 = nn.LayerNorm(channels)
        self.proj = nn.Linear(channels, channels)

    def forward(self, x, context=None):
        B, C, H, W = x.shape
        seq = run_op("spatial_flatten",
                     lambda v: jnp.swapaxes(v.reshape(v.shape[0], v.shape[1], -1), 1, 2),
                     [self.norm(x)])
        h = seq + self.self_attn(seq, seq, seq)
        if context is not None:
            ctx = context if isinstance(context, Tensor) else to_tensor(context)
            h = h + self.cross_attn(self.norm2(h), ctx, ctx)
        h = self.proj(h)
        out = run_op(
            "spatial_unflatten",
            lambda v, hh=H, ww=W: jnp.swapaxes(v, 1, 2).reshape(
                v.shape[0], v.shape[2], hh, ww),
            [h])
        return x + out


class UNetModel(nn.Layer):
    """forward(x [B,C,H,W], timesteps [B], context [B,L,D]) -> [B,C,H,W]."""

    def __init__(self, cfg: UNetConfig):
        super().__init__()
        self.config = cfg
        ch = cfg.base_channels
        emb_dim = ch * 4
        self.time_mlp1 = nn.Linear(ch, emb_dim)
        self.time_mlp2 = nn.Linear(emb_dim, emb_dim)
        self.conv_in = nn.Conv2D(cfg.in_channels, ch, 3, padding=1)

        self.down_blocks = nn.LayerList()
        self.down_attns = nn.LayerList()
        self.downsamples = nn.LayerList()
        chans = [ch]
        cur = ch
        for lvl, mult in enumerate(cfg.channel_mult):
            out_c = ch * mult
            for _ in range(cfg.num_res_blocks):
                self.down_blocks.append(ResBlock(cur, out_c, emb_dim, cfg.groups))
                self.down_attns.append(
                    AttnBlock(out_c, cfg.num_heads, cfg.context_dim, cfg.groups)
                    if lvl in cfg.attention_levels else None)
                cur = out_c
                chans.append(cur)
            if lvl < len(cfg.channel_mult) - 1:
                self.downsamples.append(nn.Conv2D(cur, cur, 3, stride=2, padding=1))
                chans.append(cur)
            else:
                self.downsamples.append(None)

        self.mid_block1 = ResBlock(cur, cur, emb_dim, cfg.groups)
        self.mid_attn = AttnBlock(cur, cfg.num_heads, cfg.context_dim, cfg.groups)
        self.mid_block2 = ResBlock(cur, cur, emb_dim, cfg.groups)

        self.up_blocks = nn.LayerList()
        self.up_attns = nn.LayerList()
        self.upsamples = nn.LayerList()
        for lvl, mult in reversed(list(enumerate(cfg.channel_mult))):
            out_c = ch * mult
            for _ in range(cfg.num_res_blocks + 1):
                skip_c = chans.pop()
                self.up_blocks.append(
                    ResBlock(cur + skip_c, out_c, emb_dim, cfg.groups))
                self.up_attns.append(
                    AttnBlock(out_c, cfg.num_heads, cfg.context_dim, cfg.groups)
                    if lvl in cfg.attention_levels else None)
                cur = out_c
            if lvl > 0:
                self.upsamples.append(nn.Conv2D(cur, cur, 3, padding=1))
            else:
                self.upsamples.append(None)

        self.norm_out = nn.GroupNorm(min(cfg.groups, cur), cur)
        self.conv_out = nn.Conv2D(cur, cfg.out_channels, 3, padding=1)
        self.act = nn.Silu()

    def forward(self, x, timesteps, context=None):
        cfg = self.config
        emb = timestep_embedding(timesteps, cfg.base_channels)
        emb = self.time_mlp2(self.act(self.time_mlp1(emb)))

        h = self.conv_in(x if isinstance(x, Tensor) else to_tensor(x))
        skips = [h]
        i = 0
        for lvl in range(len(cfg.channel_mult)):
            for _ in range(cfg.num_res_blocks):
                h = self.down_blocks[i](h, emb)
                if self.down_attns[i] is not None:
                    h = self.down_attns[i](h, context)
                skips.append(h)
                i += 1
            if self.downsamples[lvl] is not None:
                h = self.downsamples[lvl](h)
                skips.append(h)

        h = self.mid_block1(h, emb)
        h = self.mid_attn(h, context)
        h = self.mid_block2(h, emb)

        i = 0
        for uidx, lvl in enumerate(reversed(range(len(cfg.channel_mult)))):
            for _ in range(cfg.num_res_blocks + 1):
                skip = skips.pop()
                h = run_op("unet_skip_cat",
                           lambda a, b: jnp.concatenate([a, b], axis=1),
                           [h, skip])
                h = self.up_blocks[i](h, emb)
                if self.up_attns[i] is not None:
                    h = self.up_attns[i](h, context)
                i += 1
            if self.upsamples[uidx] is not None:
                h = run_op(
                    "unet_upsample",
                    lambda v: jnp.repeat(jnp.repeat(v, 2, axis=2), 2, axis=3),
                    [h])
                h = self.upsamples[uidx](h)

        return self.conv_out(self.act(self.norm_out(h)))
