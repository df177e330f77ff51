"""The AST-extracted wire-protocol registry: frames, tokens, codecs.

The reference's protocol surface is machine-readable by construction —
typed FlowTransport endpoints with FileIdentifiers, WellKnownEndpoints.h
tokens, `serializer(ar, f1, f2, ...)` field lists the flatbuffers pass
walks (fdbrpc/fdbrpc.h, flow/flat_buffers.h). This framework's wire
layer is hand-rolled Python, so the equivalent inventory is extracted
here, statically, from the source of `wire/codec.py`,
`wire/transport.py`, and `cluster/multiprocess.py`:

* every frame id registered with `codec.register(...)` — both the
  declarative `_message(id, "Name", [fields])` frames and the
  hand-written encode/decode pairs,
* every `TOKEN_*` RPC endpoint constant,
* every `server.register(TOKEN_X, handler)` dispatch binding,
* every client-side `conn.call(TOKEN_X, ...)` site (with its timeout
  and error-classification posture),
* the ordered primitive-op stream of each hand-written encoder and
  decoder (the field-drift comparison surface), and
* which frames carry a generation `epoch` (the fencing contract).

One extraction, three consumers (one copy or they drift): the `wire.*`
flowcheck family (`rules_wire.py`), the checked-in
`analysis/wire_manifest.json`, and the structure-aware codec fuzzer
(`scripts/wire_fuzz.py`) — the fuzzer mutates exactly the frames the
static pass accounts for.

stdlib-`ast` only, like the rest of flowcheck: nothing here imports the
scanned modules.
"""

from __future__ import annotations

import ast
import dataclasses
from pathlib import Path

#: primitive codec ops (codec.w_*/r_* suffixes the stream extractor
#: treats as leaves rather than helper calls)
PRIM_KINDS = {
    "u8", "u16", "u32", "i64", "u64", "bytes", "str", "bool", "mutation",
}

#: wire-layout expansion to fixed primitives, for comparing an encoder
#: stream against its paired decoder even when one side hand-rolls a
#: composite (e.g. r_resolve_columnar reads a u32 length + raw slice
#: where the encoder called w_bytes)
_EXPAND = {
    "u8": ("u8",),
    "u16": ("u16",),
    "u32": ("u32",),
    "i64": ("i64",),
    "u64": ("u64",),
    "bool": ("u8",),
    "bytes": ("u32", "raw"),
    "str": ("u32", "raw"),
    "mutation": ("u8", "u32", "raw", "u32", "raw"),
    "raw": ("raw",),
}

#: except-clause types that count as classifying a wire RPC's failure
#: (wire.unclassified-error): the transport classification, the asyncio/OS
#: errors a call can surface, and the broad catches control-plane
#: callers use deliberately. CancelledError alone is NOT classification.
CLASSIFIER_LEAVES = {
    "RemoteError", "TransportError", "ChecksumError", "HandshakeError",
    "UnknownEndpointError", "ConnectionError", "OSError", "IOError",
    "TimeoutError", "Exception", "BaseException",
}


@dataclasses.dataclass(frozen=True)
class TokenDecl:
    name: str
    value: int
    path: str
    node: ast.AST


@dataclasses.dataclass(frozen=True)
class FrameDecl:
    type_id: int
    name: str
    #: "message" (declarative `_message` frame) or "handwritten"
    #: (explicit codec.register with named encode/decode functions)
    style: str
    path: str
    node: ast.AST
    #: (field, kind) pairs for "message" frames; None for handwritten
    fields: tuple | None = None
    encoder: str | None = None
    decoder: str | None = None


@dataclasses.dataclass(frozen=True)
class HandlerReg:
    token: str          # TOKEN_* constant name at the register site
    handler: str | None  # method/function name the token dispatches to
    path: str
    node: ast.AST


@dataclasses.dataclass(frozen=True)
class HandlerDef:
    cls: str | None     # enclosing class name, None for module functions
    method: str
    frame: str          # the request parameter's annotated frame type
    path: str
    node: ast.AST       # the AsyncFunctionDef


@dataclasses.dataclass(frozen=True)
class CallSite:
    token: str          # TOKEN_* leaf, or "token" for forwarding wrappers
    has_timeout: bool   # an explicit timeout= keyword (not None)
    classified: bool    # lexically covered by a classifying except clause
    path: str
    node: ast.AST


@dataclasses.dataclass
class WireFacts:
    """Everything the wire pass needs from ONE module's AST — computed
    once per file and memoized on the FileContext, so the flowcheck
    tree check, the manifest writer, and the fuzzer's registry build
    all share the same walk."""

    path: str
    tokens: list = dataclasses.field(default_factory=list)
    frames: list = dataclasses.field(default_factory=list)
    handler_regs: list = dataclasses.field(default_factory=list)
    handler_defs: list = dataclasses.field(default_factory=list)
    call_sites: list = dataclasses.field(default_factory=list)
    #: name -> FunctionDef for every w_*/r_*/_w_*/_r_* codec function
    codec_funcs: dict = dataclasses.field(default_factory=dict)
    protocol_version: int | None = None


def _leaf(node: ast.AST) -> str | None:
    """Last segment of a Name/attribute chain: `mp.TOKEN_X` -> TOKEN_X."""
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return None


def _const_int(node: ast.AST) -> int | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return node.value
    return None


def _const_str(node: ast.AST) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _annotation_name(node: ast.AST | None) -> str | None:
    """Frame type named by a parameter annotation — `TLogPush`,
    `"TLogPop"` (string annotation), or `mp.StatusRequest`."""
    if node is None:
        return None
    s = _const_str(node)
    if s is not None:
        return s.rsplit(".", 1)[-1]
    leaf = _leaf(node)
    return leaf


def _classifying(handlers: list) -> bool:
    for h in handlers:
        if h.type is None:  # bare except
            return True
        types = h.type.elts if isinstance(h.type, ast.Tuple) else [h.type]
        for t in types:
            if _leaf(t) in CLASSIFIER_LEAVES:
                return True
    return False


def _is_wire_call(node: ast.AST) -> tuple[str, bool] | None:
    """(token_leaf, has_explicit_timeout) when `node` is a wire RPC
    call: `<conn>.call(TOKEN_X, ...)` or a forwarding wrapper's
    `<conn>.call(token, ...)`."""
    if not (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr == "call"
            and node.args):
        return None
    tok = _leaf(node.args[0])
    if tok is None or not (tok.startswith("TOKEN_") or tok == "token"):
        return None
    has_timeout = any(
        k.arg == "timeout"
        and not (isinstance(k.value, ast.Constant) and k.value.value is None)
        for k in node.keywords
    )
    return tok, has_timeout


def _scan_calls(node: ast.AST, covered: bool, path: str, out: list) -> None:
    """Collect wire call sites with their lexical try/except coverage.
    `covered` is true inside a try body whose handlers include a
    classifying exception type; function boundaries reset it (errors do
    not propagate lexically across a nested def)."""
    if isinstance(node, ast.Try):
        inner = covered or _classifying(node.handlers)
        for n in node.body:
            _scan_calls(n, inner, path, out)
        for h in node.handlers:
            for n in h.body:
                _scan_calls(n, covered, path, out)
        for n in list(node.orelse) + list(node.finalbody):
            _scan_calls(n, covered, path, out)
        return
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        body = node.body if not isinstance(node, ast.Lambda) else [node.body]
        for n in body:
            _scan_calls(n, False, path, out)
        return
    hit = _is_wire_call(node)
    if hit is not None:
        tok, has_timeout = hit
        out.append(CallSite(
            token=tok, has_timeout=has_timeout, classified=covered,
            path=path, node=node,
        ))
    for n in ast.iter_child_nodes(node):
        _scan_calls(n, covered, path, out)


def file_facts(tree: ast.Module, path: str) -> WireFacts:
    """Extract one module's wire facts. Pure: AST in, facts out."""
    facts = WireFacts(path=path)

    # module-level constants: TOKEN_* table and PROTOCOL_VERSION
    for stmt in tree.body:
        if not (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)):
            continue
        name = stmt.targets[0].id
        value = _const_int(stmt.value)
        if value is None:
            continue
        if name.startswith("TOKEN_"):
            facts.tokens.append(TokenDecl(name, value, path, stmt))
        elif name == "PROTOCOL_VERSION":
            facts.protocol_version = value

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            fname = node.name
            if fname.startswith(("w_", "r_", "_w_", "_r_")):
                facts.codec_funcs[fname] = node
            if isinstance(node, ast.AsyncFunctionDef) and node.args.args:
                args = node.args.args
                req = args[1] if args[0].arg == "self" and len(args) > 1 \
                    else args[0]
                frame = _annotation_name(req.annotation)
                if frame:
                    facts.handler_defs.append(HandlerDef(
                        cls=None, method=fname, frame=frame,
                        path=path, node=node,
                    ))
            continue
        if not isinstance(node, ast.Call):
            continue
        leaf = _leaf(node.func)
        if leaf == "_message" and len(node.args) >= 3:
            type_id = _const_int(node.args[0])
            name = _const_str(node.args[1])
            fields_node = node.args[2]
            if type_id is None or name is None \
                    or not isinstance(fields_node, ast.List):
                continue
            fields = []
            for elt in fields_node.elts:
                if isinstance(elt, ast.Tuple) and len(elt.elts) >= 2:
                    f, k = _const_str(elt.elts[0]), _const_str(elt.elts[1])
                    if f is not None and k is not None:
                        fields.append((f, k))
            facts.frames.append(FrameDecl(
                type_id=type_id, name=name, style="message", path=path,
                node=node, fields=tuple(fields),
            ))
        elif leaf == "register" and len(node.args) == 4 \
                and _const_int(node.args[0]) is not None:
            facts.frames.append(FrameDecl(
                type_id=_const_int(node.args[0]),
                name=_leaf(node.args[1]) or "?",
                style="handwritten", path=path, node=node,
                encoder=_leaf(node.args[2]), decoder=_leaf(node.args[3]),
            ))
        elif leaf == "register" and len(node.args) == 2:
            tok = _leaf(node.args[0])
            if tok is None or not tok.startswith("TOKEN_"):
                continue
            h = node.args[1]
            handler: str | None = None
            if isinstance(h, ast.Name):
                handler = h.id
            elif isinstance(h, ast.Attribute):
                handler = h.attr
            elif isinstance(h, ast.Call) and _leaf(h.func) == "route" \
                    and len(h.args) == 2:
                handler = _const_str(h.args[1])
            facts.handler_regs.append(HandlerReg(
                token=tok, handler=handler, path=path, node=node,
            ))

    # attach class names to handler defs (the annotation walk above sees
    # methods without their enclosing class)
    cls_of: dict[int, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef):
            for item in node.body:
                if isinstance(item, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    cls_of[id(item)] = node.name
    facts.handler_defs = [
        dataclasses.replace(hd, cls=cls_of.get(id(hd.node)))
        for hd in facts.handler_defs
    ]

    _scan_calls(tree, False, path, facts.call_sites)
    return facts


def facts_of(ctx) -> WireFacts:
    """Per-FileContext memoized facts: the flowcheck run computes each
    module's facts at most once no matter how many wire rules ask."""
    cached = getattr(ctx, "_wire_facts", None)
    if cached is None:
        cached = file_facts(ctx.tree, ctx.path)
        ctx._wire_facts = cached
    return cached


# ---------------------------------------------------------------------------
# Encoder/decoder op-stream extraction (wire.codec-field-drift).


def _loop_tag(iter_node: ast.AST) -> str:
    """Loops over COLUMNAR_LAYOUT pair up across enc/dec by construction
    (both sides iterate the ONE pinned layout constant)."""
    for sub in ast.walk(iter_node):
        if isinstance(sub, ast.Name) and sub.id == "COLUMNAR_LAYOUT":
            return "layout"
    return "loop"


def _branch_ops(stmts: list, extractor) -> tuple:
    ops = extractor(stmts)
    return tuple(ops)


def encoder_ops(fn: ast.FunctionDef) -> list:
    """Ordered (unexpanded) op stream of a hand-written encoder: w_KIND
    calls become KIND, helper calls become ("call", suffix), put_raw
    becomes "raw", loops nest."""

    def walk(stmts: list) -> list:
        ops: list = []
        for s in stmts:
            if isinstance(s, ast.Expr) and isinstance(s.value, ast.Call):
                leaf = _leaf(s.value.func)
                if leaf == "put_raw":
                    ops.append("raw")
                elif leaf and leaf.lstrip("_").startswith("w_"):
                    kind = leaf.lstrip("_")[2:]
                    ops.append(kind if kind in PRIM_KINDS
                               else ("call", kind))
            elif isinstance(s, (ast.For, ast.AsyncFor)):
                ops.append((_loop_tag(s.iter), _branch_ops(s.body, walk)))
            elif isinstance(s, ast.If):
                body, orelse = walk(s.body), walk(s.orelse)
                if body or orelse:
                    ops.append(("cond", tuple(body), tuple(orelse)))
        return ops

    return walk(fn.body)


def decoder_ops(fn: ast.FunctionDef) -> list:
    """Ordered (unexpanded) op stream of a hand-written decoder: r_KIND
    reads become KIND, helper reads ("call", suffix), np.frombuffer and
    manual buf[off:off+n] slices become "raw". Validation-only branches
    (raise CodecError) are transparent — raises reject, they don't read."""

    def value_ops(v: ast.AST) -> list:
        if isinstance(v, ast.Call):
            leaf = _leaf(v.func)
            if leaf == "frombuffer":
                return ["raw"]
            if leaf and leaf.lstrip("_").startswith("r_"):
                kind = leaf.lstrip("_")[2:]
                return [kind if kind in PRIM_KINDS else ("call", kind)]
        elif isinstance(v, ast.Subscript) and isinstance(v.slice, ast.Slice):
            return ["raw"]
        return []

    def walk(stmts: list) -> list:
        ops: list = []
        for s in stmts:
            if isinstance(s, ast.Assign):
                ops.extend(value_ops(s.value))
            elif isinstance(s, (ast.For, ast.AsyncFor)):
                ops.append((_loop_tag(s.iter), _branch_ops(s.body, walk)))
            elif isinstance(s, ast.If):
                body, orelse = walk(s.body), walk(s.orelse)
                if body or orelse:
                    ops.append(("cond", tuple(body), tuple(orelse)))
        return ops

    return walk(fn.body)


def expand_ops(ops: list, funcs: dict, side: str, _depth: int = 0) -> list:
    """Expand an op stream to fixed primitives + loop structure so an
    encoder and decoder compare even when their helper granularity
    differs (w_bytes vs r_u32 + raw slice). `side` picks which helper
    family ("w" or "r") resolves ("call", name) ops."""
    if _depth > 8:  # codec helpers don't recurse; bound it anyway
        return [("opaque", "depth")]
    out: list = []
    for op in ops:
        if isinstance(op, str):
            out.extend(_EXPAND.get(op, (op,)))
        elif op[0] == "call":
            fn = funcs.get(f"{side}_{op[1]}") or funcs.get(f"_{side}_{op[1]}")
            if fn is None:
                out.append(("opaque", op[1]))
            else:
                sub = encoder_ops(fn) if side == "w" else decoder_ops(fn)
                out.extend(expand_ops(sub, funcs, side, _depth + 1))
        elif op[0] in ("loop", "layout"):
            out.append((op[0],
                        tuple(expand_ops(list(op[1]), funcs, side,
                                         _depth + 1))))
        elif op[0] == "cond":
            out.append(("cond",
                        tuple(expand_ops(list(op[1]), funcs, side,
                                         _depth + 1)),
                        tuple(expand_ops(list(op[2]), funcs, side,
                                         _depth + 1))))
    return out


def ops_signature(ops: list) -> str:
    """Human-readable serialization of an (unexpanded) op stream — the
    manifest's layout string for hand-written frames."""
    parts = []
    for op in ops:
        if isinstance(op, str):
            parts.append(op)
        elif op[0] == "call":
            parts.append(op[1])
        elif op[0] in ("loop", "layout"):
            parts.append(f"{op[0]}[{ops_signature(list(op[1]))}]")
        elif op[0] == "cond":
            parts.append(
                f"cond[{ops_signature(list(op[1]))}"
                f"/{ops_signature(list(op[2]))}]"
            )
    return " ".join(parts)


def encoder_fields(fn: ast.FunctionDef) -> set[str]:
    """Field names the encoder reads off its message parameter."""
    if len(fn.args.args) < 2:
        return set()
    msg = fn.args.args[1].arg
    return {
        node.attr for node in ast.walk(fn)
        if isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name) and node.value.id == msg
    }


def decoder_fields(fn: ast.FunctionDef) -> set[str]:
    """Field names the decoder's constructed message receives (the
    keywords of the returned `(Cls(...), off)` call)."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Return) and isinstance(node.value, ast.Tuple) \
                and node.value.elts \
                and isinstance(node.value.elts[0], ast.Call):
            return {k.arg for k in node.value.elts[0].keywords if k.arg}
    return set()


# ---------------------------------------------------------------------------
# Tree-level aggregation.


@dataclasses.dataclass
class WireRegistry:
    tokens: list
    frames: list
    handler_regs: list
    handler_defs: list
    call_sites: list
    codec_funcs: dict            # name -> (path, FunctionDef)
    protocol_version: int | None

    def epoch_frames(self) -> set[str]:
        """Frames carrying a generation epoch: a declared `epoch` field,
        or a hand-written encoder that writes `msg.epoch`."""
        out = set()
        for f in self.frames:
            if f.style == "message":
                if any(name == "epoch" for name, _k in f.fields or ()):
                    out.add(f.name)
            elif f.encoder:
                entry = self.codec_funcs.get(f.encoder)
                if entry and "epoch" in encoder_fields(entry[1]):
                    out.add(f.name)
        return out

    def manifest(self) -> dict:
        """The checked-in wire_manifest.json payload: protocol version,
        frame id -> name + layout, token name -> id."""
        frames: dict[str, dict] = {}
        for f in sorted(self.frames, key=lambda f: f.type_id):
            if f.style == "message":
                layout = " ".join(f"{n}:{k}" for n, k in f.fields or ())
            else:
                entry = self.codec_funcs.get(f.encoder or "")
                layout = ops_signature(encoder_ops(entry[1])) if entry \
                    else "?"
            frames[f"0x{f.type_id:04x}"] = {"name": f.name, "layout": layout}
        tokens = {
            t.name: f"0x{t.value:04x}"
            for t in sorted(self.tokens, key=lambda t: (t.name, t.value))
        }
        pv = None if self.protocol_version is None \
            else f"0x{self.protocol_version:012x}"
        return {"protocol_version": pv, "frames": frames, "tokens": tokens}


def aggregate(all_facts: list[WireFacts]) -> WireRegistry:
    reg = WireRegistry(
        tokens=[], frames=[], handler_regs=[], handler_defs=[],
        call_sites=[], codec_funcs={}, protocol_version=None,
    )
    for facts in all_facts:
        reg.tokens.extend(facts.tokens)
        reg.frames.extend(facts.frames)
        reg.handler_regs.extend(facts.handler_regs)
        reg.handler_defs.extend(facts.handler_defs)
        reg.call_sites.extend(facts.call_sites)
        for name, fn in facts.codec_funcs.items():
            reg.codec_funcs.setdefault(name, (facts.path, fn))
        if facts.protocol_version is not None:
            reg.protocol_version = facts.protocol_version
    return reg


def load_repo_registry(root: Path | None = None) -> WireRegistry:
    """Standalone entry point (scripts/wire_fuzz.py): parse the package
    and aggregate — the SAME extraction the flowcheck gate runs, without
    importing any scanned module."""
    from foundationdb_tpu.analysis import walker

    root = root or Path(__file__).resolve().parents[2]
    all_facts = []
    for path in walker.discover(root):
        rel = path.relative_to(root).as_posix()
        if rel.startswith("foundationdb_tpu/analysis/"):
            continue
        try:
            tree = ast.parse(path.read_text(encoding="utf-8"), filename=rel)
        except SyntaxError:
            continue
        all_facts.append(file_facts(tree, rel))
    return aggregate(all_facts)
