"""``breakdown.py`` with the Sarvam cell's scopes named.

    python3 benchmark/breakdown_sarvam.py sarvam-105b.serve.longdoc [--program step]

The same JSON object as ``breakdown.py`` (which this leaves as it is), the
device's seconds by scope counted under the latent attention's, the expert
layer's and the head's scopes. It measures nothing."""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmark import breakdown  # noqa: E402

SCOPES = ("mla_q", "mla_latent", "attn_mla_prefill", "attn_mla_decode", "shared_expert",
          "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "mlp", "lm_head",
          "sample")

if __name__ == "__main__":
    breakdown.SERVE_SCOPES = SCOPES
    sys.exit(breakdown.main())
