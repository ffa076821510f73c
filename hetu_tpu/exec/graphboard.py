"""Graph visualization — the reference's graphboard (python/graphboard/
graph2fig.py:11 renders the executor DAG with graphviz behind a tiny HTTP
page).

TPU-native: the graph is the jaxpr.  ``to_dot`` renders any traceable
function (or an already-made jaxpr) as graphviz dot text; ``show`` serves it
over HTTP, rendering to SVG via the ``dot`` binary when present and falling
back to the raw dot source otherwise (zero hard dependencies).
"""

from __future__ import annotations

import html
import itertools
import shutil
import subprocess
from typing import Any, Callable, Optional

__all__ = ["to_dot", "render_svg", "show"]

_PALETTE = {
    "dot_general": "#c6dbef", "conv_general_dilated": "#c6dbef",
    "add": "#e5f5e0", "mul": "#e5f5e0", "sub": "#e5f5e0", "div": "#e5f5e0",
    "reduce_sum": "#fee6ce", "reduce_max": "#fee6ce", "reduce_min": "#fee6ce",
    "custom_jvp_call": "#ddd", "pjit": "#fde0ef",
    "broadcast_in_dim": "#f7f7f7", "reshape": "#f7f7f7",
    "transpose": "#f7f7f7", "concatenate": "#f7f7f7",
}


def _avals(v) -> str:
    a = v.aval
    shape = "x".join(map(str, a.shape)) if a.shape else "scalar"
    return f"{a.dtype}[{shape}]"


def to_dot(fn_or_jaxpr: Any, *example_args, name: str = "hetu_tpu",
           collapse_calls: bool = True) -> str:
    """Graphviz dot text for a function's jaxpr (or a ClosedJaxpr).

    ``collapse_calls`` keeps pjit/custom_jvp sub-jaxprs as single boxes
    (layer-level view); pass False to inline them (kernel-level view).
    """
    import jax

    if hasattr(fn_or_jaxpr, "jaxpr"):
        closed = fn_or_jaxpr
    else:
        closed = jax.make_jaxpr(fn_or_jaxpr)(*example_args)

    lines = [f'digraph "{name}" {{',
             '  rankdir=TB; node [shape=box, style="rounded,filled", '
             'fillcolor="#f7f7f7", fontname="Helvetica", fontsize=10];']
    counter = itertools.count()
    node_of: dict[int, str] = {}

    def node_id() -> str:
        return f"n{next(counter)}"

    def declare(nid: str, label: str, color: str = "#f7f7f7",
                shape: str = "box"):
        lines.append(f'  {nid} [label="{html.escape(label)}", '
                     f'fillcolor="{color}", shape={shape}];')

    def walk(jaxpr, prefix: str):
        for v in jaxpr.constvars:
            nid = node_id()
            node_of[id(v)] = nid
            declare(nid, f"const\n{_avals(v)}", "#fff7bc", "ellipse")
        for i, v in enumerate(jaxpr.invars):
            nid = node_id()
            node_of[id(v)] = nid
            declare(nid, f"{prefix}in{i}\n{_avals(v)}", "#deebf7", "ellipse")
        from jax.extend.core import Literal
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            inner = (eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
                     if prim in ("pjit", "custom_jvp_call", "custom_vjp_call",
                                 "remat", "checkpoint") else None)
            if inner is not None and not collapse_calls:
                inner_jaxpr = inner.jaxpr if hasattr(inner, "jaxpr") else inner
                walk(inner_jaxpr, prefix + prim + ".")
                # connect call boundary by aliasing vars
                for outer_v, inner_v in zip(eqn.invars, inner_jaxpr.invars):
                    if not isinstance(outer_v, Literal) and id(outer_v) in node_of:
                        lines.append(
                            f'  {node_of[id(outer_v)]} -> {node_of[id(inner_v)]} '
                            '[style=dashed];')
                for outer_v, inner_v in zip(eqn.outvars, inner_jaxpr.outvars):
                    if id(inner_v) in node_of:
                        node_of[id(outer_v)] = node_of[id(inner_v)]
                continue
            nid = node_id()
            label = prim
            if inner is not None:
                fn_name = eqn.params.get("name", "")
                label = f"{prim}\n{fn_name}" if fn_name else prim
            label += "\n" + ", ".join(_avals(v) for v in eqn.outvars[:2])
            declare(nid, label, _PALETTE.get(prim, "#f7f7f7"))
            for v in eqn.invars:
                if isinstance(v, Literal):
                    continue
                src = node_of.get(id(v))
                if src:
                    lines.append(f'  {src} -> {nid};')
            for v in eqn.outvars:
                node_of[id(v)] = nid
        return jaxpr.outvars

    outvars = walk(closed.jaxpr, "")
    for i, v in enumerate(outvars):
        nid = node_id()
        declare(nid, f"out{i}\n{_avals(v)}", "#fcbba1", "ellipse")
        src = node_of.get(id(v))
        if src:
            lines.append(f'  {src} -> {nid};')
    lines.append("}")
    return "\n".join(lines)


def render_svg(dot_text: str) -> Optional[str]:
    """SVG via the graphviz `dot` binary, or None when unavailable."""
    exe = shutil.which("dot")
    if exe is None:
        return None
    out = subprocess.run([exe, "-Tsvg"], input=dot_text.encode(),
                         capture_output=True)
    if out.returncode != 0:
        return None
    return out.stdout.decode()


def show(fn: Callable, *example_args, port: int = 9001,
         open_browser: bool = False, blocking: bool = True):
    """Serve the graph on http://localhost:port (graph2fig.py:11 ``show``)."""
    from http.server import BaseHTTPRequestHandler, HTTPServer

    dot_text = to_dot(fn, *example_args)
    svg = render_svg(dot_text)
    body = svg if svg is not None else f"<pre>{html.escape(dot_text)}</pre>"
    page = f"<html><head><title>hetu-tpu graphboard</title></head><body>{body}</body></html>"

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802
            payload = dot_text.encode() if self.path == "/dot" else page.encode()
            ctype = "text/plain" if self.path == "/dot" else "text/html"
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        def log_message(self, *a):
            pass

    server = HTTPServer(("127.0.0.1", port), Handler)
    if open_browser:
        import webbrowser
        webbrowser.open(f"http://127.0.0.1:{server.server_address[1]}/")
    if blocking:
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            pass
        finally:
            server.server_close()
    return server
