"""Plain float32 trunk of the grid-CNN policy (see ``forward.py``), and the
FLOPs its forward pass needs per row, from shapes alone: every size is a
leaf's, so the configuration's ``settings`` are taken and not read."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .forward import conv, dense, layer_norm


def trunk(enc, obs, quant, settings=None):
    x = obs.astype(jnp.float32)
    for i in range(3):
        x = conv(x, enc[f"Conv_{i}"], (2, 1) if i else (1, 1), quant)
        x = jax.nn.silu(layer_norm(x, enc[f"LayerNorm_{i}"]))
    x = x.reshape(x.shape[0], -1)
    x = dense(x, enc["Dense_0"], quant)
    return jax.nn.silu(layer_norm(x, enc["LayerNorm_3"]))


def forward_flops_per_row(params, settings=None) -> float:
    """Multiply-adds x2 of the convolutions (counted per OUTPUT position:
    a conv kernel is reused at every grid position), the dense block and
    the two heads, for one observation row. ``params`` may be shapes.
    The input grid's height/width are recovered from the dense block's
    fan-in: SAME padding, stride 1 then (2,1) twice, so the last conv's
    output has H/4 rows (H divisible by 4) and W columns; they enter only
    through the number of output positions of each conv."""
    p = params["params"]
    enc = p["encoder"]
    c_last = enc["Conv_2"]["kernel"].shape[-1]
    positions_last = enc["Dense_0"]["kernel"].shape[0] // c_last   # H/4 * W
    positions = [positions_last * 4, positions_last * 2, positions_last]
    flops = 0.0
    for i, pos in enumerate(positions):
        kh, kw, cin, cout = enc[f"Conv_{i}"]["kernel"].shape
        flops += 2.0 * pos * kh * kw * cin * cout
    for k in (enc["Dense_0"]["kernel"], p["policy"]["kernel"],
              p["value"]["kernel"]):
        flops += 2.0 * k.shape[0] * k.shape[1]
    return flops
