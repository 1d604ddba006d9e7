"""Operational tooling around the framework core.

- ``prepare_wikitext``: reference-exact corpus tokenization (join + tokenize).
- ``pallas_probe``: on-silicon parity of every codec kernel twin against its
  jnp codec (``chip_smoke.py`` runs it on the chip).
- ``wb_preflight``: AOT memory-analysis window-batch preflight (never OOM the
  device allocator).
- ``check_reproduction``: machine-check a sweep against the reference's
  golden PPL anchors (the REPRODUCING.md north star).
"""
