"""Weights for ``kind: serve_window_moe``: random, from ``--seed``, drawn on
the device in ONE jitted call and handed out in the dtype the configuration
stores them in (``assumed.param_dtype``) — no float32 tree beside them.

The rule is ``weights_latent_moe.py``'s, by each leaf's name, and nothing
more: this decoder has no leaf that rule does not know (kernels N(0, 1 /
fan-in), a stacked expert kernel's fan-in its second-to-last dim; the
embedding N(0, 1); norm scales 1 + 0.1 N(0, 1)). The file exists so that
the kind's driver names the kind's own weights, as the other kinds' do.
"""

from benchmark.weights_latent_moe import leaf_rule, make_weights  # noqa: F401
