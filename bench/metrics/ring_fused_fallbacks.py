"""Collectives of the compiled step that were asked to run as a fused ring
kernel, failed the kernel's gate and took the ppermute ring instead
(``repro.core.overlap.fused_fallbacks``, recorded while the step traced)."""


def read(m):
    return len(m.fallbacks)
