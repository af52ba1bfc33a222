"""The flash-attention kernels (``repro.kernels.flash_attention``).  The
substring also tags the two backward calls, ``flash_attention_dkdv`` and
``flash_attention_dq``, so ``flash_attention_roofline`` sets all three
against the forward's count."""

import flops

MATCH = ("flash_attention",)


def call(cfg, rc, traffic):
    """The forward's count (:func:`flops.flash_attention`) where the step
    has attention layers and runs them with ``attention_impl="flash"``.
    The kernel skips the tiles a sliding window hides, so a call counts
    the visible pairs; where the attention layers' windows differ, their
    mean, as each layer makes the same number of calls."""
    windows = [s.sliding_window for s in cfg.pattern if s.kind == "attn"]
    if rc.attention_impl != "flash" or not windows:
        return None
    b, s = traffic["batch"], traffic["seq"]
    h, kh, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    if len(set(windows)) == 1:
        return flops.flash_attention(b, s, h, kh, d, window=windows[0])
    calls = [flops.flash_attention(b, s, h, kh, d, window=w) for w in windows]
    return (sum(f for f, _ in calls) / len(calls), calls[0][1])
