#!/usr/bin/env python3
"""Bake-off of a page pool's PHYSICAL FORM (PR 37), at the geometry
``deepseek-v3.long-doc-qa`` runs: 16 slots, views 8,192 and 16,384 wide,
5 layers, a bf16 latent row of 576 in a pool of 16,385 pages of 16.

    python tools/kv_layout_bakeoff.py             # compiles for a described v5e
    chiprun -- python tools/kv_layout_bakeoff.py  # compiles and times on the chip

One program a form: per layer, gather the view, write each row's new latent
into it, one score einsum and one sum einsum against a (16, 128, width)
query (the absorbed decode attention's pair), and the 16-row write-back; the
pool is donated, as the engine's programs donate theirs. Six latent forms:

- ``rows576``       (rows, 576), row gather: the parent's (PR 34);
- ``pages576``      (pages, 16, 576), page gather;
- ``rows640``       (rows, 640), row gather, the row padded to whole lanes;
- ``pagerow9216``   (pages, 9216), a page as one row;
- ``pages72x128``   (pages, 72, 128), a page as whole tiles of its bytes;
- ``pages640``      (pages, 16, 640), page gather: what ``serve/paged_kv.py``
  ships, through its own accessors (``take_pages`` / ``set_page_rows``);
  ``pages640.slice`` slices the view back to 576 once after the gather (the
  model's row stays 576), ``pages640.pad_q`` keeps the view 640 wide and
  pads the query with zeros (a model whose row type is lane-whole).

For the record only, the 8B geometry (16 x 1,024, 36 layers of k and v, 8
heads of 128): ``kv.rows`` (rows, 8, 128) row gather, what ships, against
``kv.pages`` (pages, 16, 8, 128) page gather.

The geometry ``mimo-v2.5.agent-context`` runs (PR 39: 16 slots x 32,768,
2 global layers of 4 K/V heads, keys 192 over values 128, pool 32,769
pages of 16), for a row with a head axis whose minor dimension is 1.5 lane
tiles: ``hyb.rows192`` (rows, 4, 192) / (rows, 4, 128), what ``init_cache``
gives the parent's code; ``hyb.flat768`` (rows, 768) / (rows, 512),
reshaped to heads after the gather; ``hyb.pages768`` (pages, 16, 768) /
(pages, 16, 512), a page at a time; ``hyb.rows256`` (rows, 4, 256) sliced
to 192 after the gather / (rows, 4, 128) (not built: no model needs it); ``hyb.pages768.flat``, the same pools as
``hyb.pages768`` attended as they are, FLAT (the heads split on the query's
side, ``ops/swa_attention.py::decode_attention``): what ships, the model's
row being one vector; and ``hyb.window_rows192`` (rows, 8, 192) / (rows, 8, 128), a window
layer held like a global one (ONE layer of it: five, 12.5 GB, do not fit).

The geometry ``trinity-large.mixed-lengths`` runs (PR 44: 16 slots x
32,768, ONE global layer of 8 K/V heads, keys and values 128, 4,096 B a
row, 48 query heads; a row is whole lane tiles either way, so what is left
to choose is the gather's unit and where the heads split): ``tri.rows8x128``
(rows, 8, 128), row gather, the Qwen3 pools' form; ``tri.pages1024.heads``
(pages, 16, 1024), page gather, the view reshaped to heads for a grouped
einsum (a page's rows written back as vectors); ``tri.pages1024.flat``, the same pools attended FLAT through the
shipped accessors and ``decode_attention`` (what ships). ``--forms tri``
runs these alone.

Prints one JSON line a form and view: the entry layout the compiler gives
the pool, the pool-shaped ``copy`` / ``transpose`` instructions in the
compiled text, the gather's ``slice_sizes``, cost-analysis bytes, and on a
chip the median milliseconds; writes them to
``chiprun_out/kv_layout_bakeoff.json``. Off the chip nothing runs and no
time is printed.
"""

from __future__ import annotations

import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from llm_in_practise_tpu.serve import paged_kv

S, P, H, D = 16, 16, 128, 576            # slots, page, heads, latent row
PAGES = S * 16384 // P + 1               # the cell's pool, + the trash page
LAYERS = 5
KV_PAGES, KV_HEADS, KV_DIM, KV_LAYERS = S * 1024 // P + 1, 8, 128, 36


def _attend(view, q, pos, new):
    """The model's part: the row's new latent written at ``pos``, scores
    and sums over the whole view (``ops/mla_attention.decode_attention``
    without its weights)."""
    view = jax.vmap(lambda v, n, i: jax.lax.dynamic_update_slice(
        v, n[None], (i, 0)))(view, new, pos)
    s = jnp.einsum("bhc,bkc->bhk", q, view,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(s, axis=-1).astype(view.dtype)
    return view, jnp.einsum("bhk,bkc->bhc", p, view)


def _rows_of(view, pos):
    idx = pos.reshape((-1, 1) + (1,) * (view.ndim - 2))
    return jnp.take_along_axis(view, idx, axis=1)[:, 0]


def _page_idx(flat):
    return jnp.divmod(flat, P)


# form -> (pool shape, index kind, gather(buf, idx, W) -> (S, W, D'),
#          write(buf, flat (S,), rows (S, D')) -> buf, width the query sees)
def _forms():
    pad = paged_kv.lane_whole(D)

    def take_rows(buf, idx, w):
        return jnp.take(buf, idx.reshape(-1), axis=0, mode="clip").reshape(
            (S, w) + buf.shape[1:])

    def take_pages(buf, idx, w):
        pages = jnp.take(buf, idx.reshape(-1), axis=0, mode="clip")
        return pages.reshape(S, w, -1)

    def set_rows(buf, flat, rows):
        return buf.at[flat].set(rows)

    def set_in_page(buf, flat, rows):
        page, off = _page_idx(flat)
        return buf.at[page, off].set(rows)

    def set_in_pagerow(buf, flat, rows):
        page, off = _page_idx(flat)
        cols = off[:, None] * D + jnp.arange(D)[None, :]
        return buf.at[page[:, None], cols].set(rows)

    def set_in_tiles(buf, flat, rows):
        return set_in_pagerow(buf.reshape(PAGES, P * D), flat,
                              rows).reshape(buf.shape)

    return {
        "rows576": ((PAGES * P, D), "rows", take_rows, set_rows, D),
        "pages576": ((PAGES, P, D), "pages", take_pages, set_in_page, D),
        "rows640": ((PAGES * P, pad), "rows", take_rows, set_rows, pad),
        "pagerow9216": ((PAGES, P * D), "pages", take_pages,
                        set_in_pagerow, D),
        "pages72x128": ((PAGES, P * D // 128, 128), "pages", take_pages,
                        set_in_tiles, D),
        "pages640.slice": (
            (PAGES, P, pad), "pages",
            lambda buf, idx, w: paged_kv.take_pages(buf, idx, D),
            paged_kv.set_page_rows, D),
        "pages640.pad_q": (
            (PAGES, P, pad), "pages",
            lambda buf, idx, w: paged_kv.take_pages(buf, idx, pad),
            paged_kv.set_page_rows, pad),
    }


def latent_program(form: str, width: int):
    """(fn, argument shapes): ``fn(pools, idx, flat, pos, q, new)`` ->
    ``(pools, sums)`` over ``LAYERS`` pools of the form."""
    shape, kind, gather, write, seen = _forms()[form]

    def fn(pools, idx, flat, pos, q, new):
        qq = jnp.pad(q, ((0, 0), (0, 0), (0, seen - D)))
        nn = jnp.pad(new, ((0, 0), (0, seen - D)))
        out, acc = [], 0.0
        for buf in pools:
            view, o = _attend(gather(buf, idx, width), qq, pos, nn)
            acc = acc + o[..., :D]
            out.append(write(buf, flat, _rows_of(view, pos)))
        return out, acc

    n_idx = width // P if kind == "pages" else width
    bf, i32 = jnp.bfloat16, jnp.int32
    args = ([jax.ShapeDtypeStruct(shape, bf)] * LAYERS,
            jax.ShapeDtypeStruct((S, n_idx), i32),
            jax.ShapeDtypeStruct((S,), i32), jax.ShapeDtypeStruct((S,), i32),
            jax.ShapeDtypeStruct((S, H, D), bf),
            jax.ShapeDtypeStruct((S, D), bf))
    return fn, args, shape


def kv_program(form: str, width: int):
    """The 8B geometry: k and v pools of ``KV_LAYERS`` layers, a grouped
    score / sum pair, the 16-row write-back."""
    by_pages = form == "kv.pages"
    shape = ((KV_PAGES, P, KV_HEADS, KV_DIM) if by_pages
             else (KV_PAGES * P, KV_HEADS, KV_DIM))

    def fn(pools, idx, flat, pos, q, new):
        out, acc = [], 0.0
        for k_buf, v_buf in pools:
            views = []
            for buf in (k_buf, v_buf):
                got = jnp.take(buf, idx.reshape(-1), axis=0, mode="clip")
                view = got.reshape(S, width, KV_HEADS, KV_DIM)
                views.append(jax.vmap(
                    lambda v, n, i: jax.lax.dynamic_update_slice(
                        v, n[None], (i, 0, 0)))(view, new, pos))
            k, v = views
            s = jnp.einsum("bghd,bkgd->bghk", q, k,
                           preferred_element_type=jnp.float32)
            p = jax.nn.softmax(s, axis=-1).astype(k.dtype)
            acc = acc + jnp.einsum("bghk,bkgd->bghd", p, v)
            pair = []
            for buf, view in zip((k_buf, v_buf), views):
                rows = _rows_of(view, pos)
                if by_pages:
                    page, off = _page_idx(flat)
                    pair.append(buf.at[page, off].set(rows))
                else:
                    pair.append(buf.at[flat].set(rows))
            out.append(tuple(pair))
        return out, acc

    bf, i32 = jnp.bfloat16, jnp.int32
    pool = jax.ShapeDtypeStruct(shape, bf)
    args = ([(pool, pool)] * KV_LAYERS,
            jax.ShapeDtypeStruct((S, width // P if by_pages else width), i32),
            jax.ShapeDtypeStruct((S,), i32), jax.ShapeDtypeStruct((S,), i32),
            jax.ShapeDtypeStruct((S, KV_HEADS, 4, KV_DIM), bf),
            jax.ShapeDtypeStruct((S, KV_HEADS, KV_DIM), bf))
    return fn, args, shape


HYB_PAGES, HYB_DQ, HYB_DV, HYB_Q = S * 32768 // P + 1, 192, 128, 64
TRI_HK, TRI_D, TRI_Q = 8, 128, 48


def hybrid_program(form: str, width: int):
    """MiMo-V2.5's geometry (``hyb.*``: key and value pools of a layer
    kind, a grouped score / sum pair with a (16, 64, 192) query over
    192-wide keys and 128-wide values) or Trinity-Large's (``tri.*``: ONE
    global layer, 8 K/V heads of 128, a (16, 48, 128) query): the gather,
    the new row's write, the pair of einsums, the 16-row write-back."""
    if form.startswith("tri."):
        hk, layers, dq, dv, n_q = TRI_HK, 1, TRI_D, TRI_D, TRI_Q
    else:
        hk, layers = (8, 1) if form == "hyb.window_rows192" else (4, 2)
        dq, dv, n_q = HYB_DQ, HYB_DV, HYB_Q
    rows = HYB_PAGES * P
    pad = paged_kv.lane_whole(dq)
    by_rows = ((rows, hk, dq), (rows, hk, dv))
    by_page = ((HYB_PAGES, P, hk * dq), (HYB_PAGES, P, hk * dv))
    shapes = {
        "hyb.rows192": by_rows, "hyb.window_rows192": by_rows,
        "hyb.flat768": ((rows, hk * dq), (rows, hk * dv)),
        "hyb.pages768": by_page, "hyb.pages768.flat": by_page,
        "hyb.rows256": ((rows, hk, pad), (rows, hk, dv)),
        "tri.rows8x128": by_rows, "tri.pages1024.heads": by_page,
        "tri.pages1024.flat": by_page,
    }[form]
    by_pages = shapes is by_page
    bf, i32 = jnp.bfloat16, jnp.int32
    args = ([tuple(jax.ShapeDtypeStruct(sh, bf) for sh in shapes)] * layers,
            jax.ShapeDtypeStruct((S, width // P if by_pages else width), i32),
            jax.ShapeDtypeStruct((S,), i32), jax.ShapeDtypeStruct((S,), i32),
            jax.ShapeDtypeStruct((S, n_q, dq), bf),
            jax.ShapeDtypeStruct((S, hk, dq), bf),
            jax.ShapeDtypeStruct((S, hk, dv), bf))
    if form.endswith(".flat"):
        return _hybrid_flat, args, shapes[0]

    def fn(pools, idx, flat, pos, q, new_k, new_v):
        out, acc = [], 0.0
        for k_buf, v_buf in pools:
            views = []
            for buf, new, dim in ((k_buf, new_k, dq), (v_buf, new_v, dv)):
                got = jnp.take(buf, idx.reshape(-1), axis=0, mode="clip")
                view = got.reshape(S, width, hk, -1)[..., :dim]
                views.append(jax.vmap(
                    lambda v, n, i: jax.lax.dynamic_update_slice(
                        v, n[None], (i, 0, 0)))(view, new, pos))
            k, v = views
            s = jnp.einsum("bgrd,bkgd->bgrk",
                           q.reshape(S, hk, n_q // hk, dq), k,
                           preferred_element_type=jnp.float32)
            p = jax.nn.softmax(s, axis=-1).astype(k.dtype)
            acc = acc + jnp.einsum("bgrk,bkgd->bgrd", p, v)
            pair = []
            for buf, view in zip((k_buf, v_buf), views):
                rows_new = _rows_of(view, pos)
                if buf.shape[-1] == pad and form == "hyb.rows256":
                    rows_new = jnp.pad(rows_new, ((0, 0), (0, 0),
                                                  (0, pad - dq)))
                rows_new = rows_new.reshape((S,) + buf.shape[
                    2 if by_pages else 1:])
                if by_pages:
                    page, off = _page_idx(flat)
                    pair.append(buf.at[page, off].set(rows_new))
                else:
                    pair.append(buf.at[flat].set(rows_new))
            out.append(tuple(pair))
        return out, acc

    return fn, args, shapes[0]


def _hybrid_flat(pools, idx, flat, pos, q, new_k, new_v):
    """The shipped path: ``paged_kv.take_pages`` / ``set_page_rows`` and
    ``swa_attention.decode_attention`` over the flat view."""
    from llm_in_practise_tpu.ops import swa_attention as swa

    out, acc = [], 0.0
    for k_buf, v_buf in pools:
        views = []
        for buf, new in ((k_buf, new_k), (v_buf, new_v)):
            view = paged_kv.take_pages(buf, idx, buf.shape[-1])
            views.append(jax.vmap(
                lambda v, n, i: jax.lax.dynamic_update_slice(
                    v, n.reshape(1, -1), (i, 0)))(view, new, pos))
        acc = acc + swa.decode_attention(q[:, None], *views, pos,
                                         scale=0.07)
        out.append(tuple(
            paged_kv.set_page_rows(buf, flat, _rows_of(view, pos))
            for buf, view in zip((k_buf, v_buf), views)))
    return out, acc


def read_text(text: str, shape: tuple) -> dict:
    """What the compiled text says of a pool of ``shape``: its entry
    layout, the ``copy`` / ``transpose`` instructions whose result is
    pool-shaped, and the slice sizes of the gathers."""
    dims = ",".join(str(d) for d in shape)
    entry = re.search(r"entry_computation_layout=\{\((.*?)\)->", text, re.S)
    layout = re.search(rf"bf16\[{dims}\](\{{[^}}]*\}})",
                       entry.group(1) if entry else text)
    copies = re.findall(
        rf"= bf16\[{dims}\]\S* (copy|transpose)\(", text)
    slices = sorted(set(re.findall(r"slice_sizes=\{([\d,]+)\}", text)))
    return {"entry_layout": layout.group(1) if layout else None,
            "pool_shaped_copies": len(copies),
            "gather_slice_sizes": slices}


def compile_for(fn, args, sharding):
    placed = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), args)
    return jax.jit(fn, donate_argnums=(0,)).lower(*placed).compile()


def timed(compiled, args, width: int, n_pages: int, reps: int = 7) -> float:
    """Median ms of ``reps`` runs on the attached chip, the pools fed
    back (donated) from run to run; indices spread over the whole pool."""
    rng = np.random.default_rng(0)
    pools, idx, flat, pos, q, *new = args
    kind_pages = idx.shape[1] != width
    pages = rng.permutation(n_pages - 1)[:S * width // P].reshape(S, -1) + 1
    rows = (pages[:, :, None] * P + np.arange(P)).reshape(S, width)
    at = np.full((S,), width * 3 // 4, np.int32)
    live = [jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), pools),
            jnp.asarray(pages if kind_pages else rows, jnp.int32),
            jnp.asarray(rows[np.arange(S), at], jnp.int32), jnp.asarray(at),
            *(jax.random.normal(jax.random.PRNGKey(i), a.shape, a.dtype)
              for i, a in enumerate((q, *new)))]
    out = []
    for _ in range(reps + 1):
        t = time.perf_counter()
        live[0], acc = compiled(*live)
        jax.block_until_ready((live[0], acc))
        out.append(time.perf_counter() - t)
    return 1e3 * float(np.median(out[1:]))


def main() -> int:
    from jax.sharding import SingleDeviceSharding

    on_chip = jax.devices()[0].platform == "tpu"
    if on_chip:
        device = jax.devices()[0]
    else:
        from jax.experimental import topologies

        jax.config.update("jax_enable_compilation_cache", False)
        device = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices[0]
    sharding = SingleDeviceSharding(device)
    lines = []
    cases = ([(latent_program, f, w, PAGES) for w in (8192, 16384)
              for f in _forms()]
             + [(kv_program, f, 1024, KV_PAGES)
                for f in ("kv.rows", "kv.pages")]
             + [(hybrid_program, f, w, HYB_PAGES) for w in (8192, 32768)
                for f in ("hyb.rows192", "hyb.flat768", "hyb.pages768",
                          "hyb.pages768.flat", "hyb.rows256",
                          "hyb.window_rows192")]
             + [(hybrid_program, f, w, HYB_PAGES) for w in (8192, 32768)
                for f in ("tri.rows8x128", "tri.pages1024.heads",
                          "tri.pages1024.flat")])
    if sys.argv[1:2] == ["--forms"]:
        cases = [c for c in cases if c[1].startswith(sys.argv[2])]
    for program, form, width, n_pages in cases:
        fn, args, shape = program(form, width)
        line = {"form": form, "view": width, "pool": list(shape),
                "timed_on": device.device_kind if on_chip else None}
        try:
            compiled = compile_for(fn, args, sharding)
            line.update(read_text(compiled.as_text(), shape))
            cost = compiled.cost_analysis() or {}
            line["bytes_accessed"] = cost.get("bytes accessed")
            if on_chip:
                line["ms"] = timed(compiled, args, width, n_pages)
        except Exception as e:      # a form the compiler or the chip refuses
            line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        lines.append(line)
        print(json.dumps(line), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "kv_layout_bakeoff.json"),
              "w", encoding="utf-8") as f:
        json.dump(lines, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
