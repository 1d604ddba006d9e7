"""Parameters a configuration of the ``lfm2_moe`` family holds and bytes a
decode step of it must move, from shapes alone. ``c`` is a configuration
file's dict: the published ``config.json`` keys (``share.experts_held`` where
fewer than ``num_experts`` are held). Every byte count is a floor (each byte
once, nothing for activations, intermediates or the copies a page gather
makes), so a share of the HBM peak computed from one cannot pass 100%.

The shares below are of the DECODE STEP's own device time: the short
convolutions and the experts run under the same scopes in a prefill, and this
family's cell admits a 2560-token prompt every other step, so
:func:`step_scope_ms` counts only the operations that ran inside the step
executable's runs (``program.step_module``), per run of it.
"""
from __future__ import annotations

import time

from benchmark.rooflines import ITEMSIZE

#: the window a short convolution keeps a slot is float32 whatever the weights
STATE_ITEMSIZE = 4
#: the scopes of a short convolution, and of a routed layer with none shared
SHORTCONV_SCOPES = ("shortconv.proj", "shortconv.conv")
MOE_SCOPES = ("moe.route", "moe.experts")
_IMPORTED_AT = time.time()
_EVENTS: dict = {}


def _layers(c: dict) -> tuple:
    """(conv layers, attention layers, dense layers, expert layers)."""
    kinds = c["layer_types"]
    dense = c["num_dense_layers"]
    return (kinds.count("conv"), kinds.count("full_attention"), dense,
            len(kinds) - dense)


def head_dim(c: dict) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def conv_mixer_params(c: dict) -> int:
    """``W_in`` (D x 3D), the taps (D x L), ``W_out`` (D x D); no bias."""
    d = c["hidden_size"]
    return 3 * d * d + d * c["conv_L_cache"] + d * d


def attention_params(c: dict) -> int:
    """wq and wo at H x hd wide, wk and wv at KV x hd, the two per-head norm
    scales; no bias, no gate."""
    d, hd = c["hidden_size"], head_dim(c)
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return 2 * d * h * hd + 2 * d * kv * hd + 2 * hd


def expert_ffn_params(c: dict) -> int:
    """The router over its published width, its selection bias and the held
    experts (SwiGLU: gate, up, down); no shared expert."""
    d, f = c["hidden_size"], c["moe_intermediate_size"]
    held = c.get("share", {}).get("experts_held", c["num_experts"])
    return d * c["num_experts"] + c["num_experts"] + 3 * held * d * f


def param_count(c: dict) -> int:
    """Every mixer, every feed-forward, two norms a layer, the tied table
    (once) and the final norm."""
    d = c["hidden_size"]
    conv, attn, dense, expert = _layers(c)
    return (conv * conv_mixer_params(c) + attn * attention_params(c)
            + dense * 3 * d * c["intermediate_size"]
            + expert * expert_ffn_params(c)
            + 2 * d * len(c["layer_types"]) + c["vocab_size"] * d + d)


def kv_row_bytes(c: dict, itemsize: int) -> int:
    """K and V of one position of ONE attention layer."""
    return 2 * c["num_key_value_heads"] * head_dim(c) * itemsize


def pool_bytes(c: dict) -> int:
    """The page pool as the serving geometry sizes it: ``num_pages`` pages
    an attention layer."""
    s = c["serving"]
    return (_layers(c)[1] * s["num_pages"] * s["page_size"]
            * kv_row_bytes(c, ITEMSIZE[c["torch_dtype"]]))


def window_bytes_per_slot(c: dict) -> int:
    """One slot's windows over the conv layers: ``L - 1`` rows of D lanes a
    layer, float32."""
    return (_layers(c)[0] * (c["conv_L_cache"] - 1) * c["hidden_size"]
            * STATE_ITEMSIZE)


def shortconv_step_bytes(c: dict, live_slots: float) -> float:
    """``shortconv.*`` in the step: every conv mixer's weights read once and
    the live slots' windows read once and written once."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    return (_layers(c)[0] * conv_mixer_params(c) * itemsize
            + 2.0 * live_slots * window_bytes_per_slot(c))


def experts_step_bytes(c: dict) -> float:
    """``moe.*`` in the step: the router, bias and held experts of every
    expert layer, read once (at 96 tokens x 4 of 32 every expert is hit)."""
    return float(_layers(c)[3] * expert_ffn_params(c)
                 * ITEMSIZE[c["torch_dtype"]])


def step_bytes(c: dict, live_tokens: float, live_slots: float) -> float:
    """The whole step: every held weight once (the tied table is read whole
    by the unembed), the live slots' windows read and written, the attention
    layers' live K/V rows read once and one new row a slot a layer
    written."""
    itemsize = ITEMSIZE[c["torch_dtype"]]
    row = _layers(c)[1] * kv_row_bytes(c, itemsize)
    return (param_count(c) * itemsize
            + 2.0 * live_slots * window_bytes_per_slot(c)
            + live_tokens * row + live_slots * row)


# -- what the readers share ---------------------------------------------------

def _events(record: dict):
    """The traced run's operations by innermost scope, executables' runs and
    host spans (``program_trace.load_program_events``), loaded once a
    process; None for an untraced run or when this process left no profile."""
    from benchmark import program_trace

    if not record.get("trace"):
        return None
    if "events" not in _EVENTS:
        found = program_trace.newest_xplane(not_before=_IMPORTED_AT)
        _EVENTS["events"] = None if found is None else (
            program_trace.load_program_events(
                found[0], program_trace.program_scopes()))
    return _EVENTS["events"]


def scope_ms_in_step(events: dict, scopes: tuple, step_module: str):
    """Device self milliseconds of the operations whose innermost scope is
    one of ``scopes`` AND that ran inside a run of the executable
    ``step_module`` in the traced window, per run of it (a chip's mean); None
    where the window holds no such run or no such operation. A pure function
    of events."""
    from benchmark.trace_reduce import WINDOW_SPAN, _clip, _self_times

    win = [(s, s + d) for name, s, d in events["host"] if name == WINDOW_SPAN]
    if not win or not events["devices"]:
        return None
    lo, hi = win[0]
    runs, seconds, seen = 0.0, 0.0, False
    for plane in events["devices"].values():
        steps = sorted((a, b) for name, a, b in _clip(
            plane.get("modules", []), lo, hi) if step_module in name)
        if not steps:
            continue
        runs += len(steps)
        inside, i = [], 0
        for op in sorted(_clip(plane["ops"], lo, hi), key=lambda ev: ev[1]):
            while i < len(steps) and steps[i][1] <= op[1]:
                i += 1
            if i < len(steps) and steps[i][0] <= op[1]:
                inside.append(op)
        own = _self_times(inside)
        seen = seen or any(s in own for s in scopes)
        seconds += sum(own.get(s, 0) for s in scopes) * 1e-9
    if not runs or not seen:
        return None
    return 1e3 * seconds / runs


def step_scope_ms(record: dict, scopes: tuple):
    """:func:`scope_ms_in_step` of this process's traced run; None without a
    trace, for a program without the scopes, or a window without a step."""
    events = _events(record)
    if events is None:
        return None
    return scope_ms_in_step(events, scopes,
                            record["config"]["program"]["step_module"])
