#!/usr/bin/env python3
"""lint_trpc — mechanical repo invariants the type system can't hold
(ISSUE 7 tentpole, run in tier-1 via tests/test_lint_trpc.py).

Rules (each names the incident class it prevents):

  flag-validator     Every runtime `Flag::define_*` whose name is a
                     `trpc_*` literal (or flows in via a variable, i.e.
                     a wrapper/per-method definition) must install a
                     set_validator / set_int_range (or
                     set_reloadable(false)) nearby.
                     Reloadable-without-validation means /flags?setvalue
                     can land garbage in a hot path at runtime.

  var-help           Every `expose(` call site must pass a description:
                     the Prometheus exposition renders it as # HELP, and
                     a bare metric name is unreadable on a dashboard
                     three PRs later.

  capi-gil           The Python boundary must release/reacquire the GIL
                     around every native call: the library loads via
                     ctypes.CDLL (never PyDLL — that HOLDS the GIL
                     through the call, so a parked fiber wait would
                     freeze the interpreter), and every capi symbol
                     Python touches declares explicit marshalling —
                     restype when the C return is a pointer/64-bit
                     (silent truncation otherwise), argtypes when it
                     takes arguments.

  tail-group         The tstd optional meta-tail is positional: encode
                     and decode must agree on the exact group sequence.
                     `// tail-group N (name)` markers in protocol.cc
                     must be unique, consecutive from 1, and identical
                     between encode_meta and decode_meta — adding a
                     sixth group to one side only is a wire break.

  digest-wire        The mergeable latency digest and the fleet
                     publication blob are binary on the wire (naming://
                     payloads, /fleet, fleet_top.py): the
                     `digest-wire N (MAGIC)` markers in
                     cpp/stat/digest.h (encoder) and
                     brpc_tpu/rpc/observe.py (decoder) must be unique,
                     consecutive from 1, and identical on both sides —
                     a one-sided layout change silently corrupts every
                     fleet merge instead of failing loudly.

  flag-exists        Every `trpc_*` flag name a Python surface, tool or
                     test references literally (set_flag/get_flag) must
                     be defined by a `Flag::define_*` in the C++ runtime.
                     A typo'd name in tooling (e.g. the ISSUE 12
                     trpc_cluster_*/trpc_drain_*/trpc_naming_* knobs)
                     otherwise only fails at run time, on the one box
                     that exercises that code path.

  tuner-rule         The self-tuning controller actuates flags named in
                     cpp/stat/tuner.cc's rule table and samples the vars
                     in its input list.  Every `tuner-knob (name)` marker
                     must sit on the line assigning that exact literal,
                     and the knob must be a defined, validated,
                     *reloadable* trpc_* flag (a typo'd knob silently
                     never tunes; an immutable one can never be
                     actuated).  Every `tuner-input` var must be exposed
                     WITH a Prometheus HELP description (names ending in
                     '_' match the dynamically-suffixed families by
                     prefix) — the controller's inputs must be
                     dashboard-readable, since /tuner republishes them.

  error-code-sync    The cpp error-code table (`constexpr int kE* = N;`
                     in cpp/net/*.h — kEOverloaded/kEDraining/
                     kEDeadlineExpired/the kv/naming/coll families) must
                     match the ERROR_CODES mirror in
                     brpc_tpu/rpc/_lib.py exactly (both directions, same
                     values), and no two names may share a code.  The
                     typed-exception constructors resolve codes through
                     the capi at run time, but a code added or
                     renumbered on one side only used to drift silently
                     until a client mis-typed an exception in
                     production.

  atomic-comment     Every memory_order_relaxed / memory_order_acquire
                     in the socket/messenger/qos/stripe hot paths must
                     carry a justification comment (same line or within
                     the 4 lines above): a bare relaxed atomic is
                     indistinguishable from a missed edge in review.

Exit 0 clean; exit 1 with one line per violation.
"""

from __future__ import annotations

import pathlib
import re
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
CPP = REPO / "cpp"
RUNTIME_DIRS = ["base", "fiber", "stat", "net", "capi"]

violations: list = []


def flag(path: pathlib.Path, line: int, rule: str, msg: str) -> None:
    violations.append(
        f"{path.relative_to(REPO)}:{line}: [{rule}] {msg}")


def runtime_files(exts=(".cc", ".h")) -> list:
    out = []
    for d in RUNTIME_DIRS:
        for p in sorted((CPP / d).iterdir()):
            if p.suffix in exts:
                out.append(p)
    return out


# ---- flag-validator ------------------------------------------------------

def check_flag_validators() -> None:
    call = re.compile(r"define_(?:bool|int64|double|string)\(")
    for path in runtime_files():
        lines = path.read_text().splitlines()
        for i, text in enumerate(lines):
            if not call.search(text):
                continue
            if ("Flag* Flag::define_" in text
                    or "static Flag* define_" in text):
                continue  # the registry's own declaration/definition
            # First argument: the rest of this line + the next (the
            # repo wraps define calls at most once before the name).
            head = text + " " + (lines[i + 1] if i + 1 < len(lines) else "")
            m = re.search(r"define_(?:bool|int64|double|string)\(\s*([^,)]+)",
                          head)
            first = m.group(1).strip() if m else ""
            if first.startswith('"') and not first.startswith('"trpc_'):
                continue  # non-trpc namespace: outside this rule
            if not first or first.startswith("//"):
                continue
            # Window stops at the NEXT define_ call: a neighbour flag's
            # set_validator must not be credited to this one.
            window_lines = [text]
            for nxt in lines[i + 1:i + 30]:
                if call.search(nxt):
                    break
                window_lines.append(nxt)
            window = "\n".join(window_lines)
            if ("set_validator" not in window
                    and "set_int_range" not in window
                    and "set_reloadable(false)" not in window):
                flag(path, i + 1, "flag-validator",
                     f"define of {first or '<flag>'} has no set_validator/"
                     "set_int_range (or set_reloadable(false)) within 30 "
                     "lines")


# ---- var-help ------------------------------------------------------------

def _expose_calls(text: str) -> list:
    """Every `.expose(` / `->expose(` call site in `text` as
    (line, first_arg, rest_args) with the split at the first
    paren/brace-depth-0 comma outside strings (rest_args = "" when the
    call has a single argument)."""
    out = []
    site = re.compile(r"[\w\])](?:\.|->)expose\(")
    for m in site.finditer(text):
        start = text.index("(", m.start() + 1)
        depth, j = 0, start
        while j < len(text):
            if text[j] == "(":
                depth += 1
            elif text[j] == ")":
                depth -= 1
                if depth == 0:
                    break
            j += 1
        args = text[start + 1:j]
        d, in_str, split_at = 0, False, -1
        k = 0
        while k < len(args):
            c = args[k]
            if in_str:
                if c == "\\":
                    k += 2
                    continue
                if c == '"':
                    in_str = False
            elif c == '"':
                in_str = True
            elif c in "([{":
                d += 1
            elif c in ")]}":
                d -= 1
            elif c == "," and d == 0:
                split_at = k
                break
            k += 1
        line = text[:m.start()].count("\n") + 1
        if split_at < 0:
            out.append((line, args, ""))
        else:
            out.append((line, args[:split_at], args[split_at + 1:]))
    return out


def check_var_help() -> None:
    for path in runtime_files():
        text = path.read_text()
        lines = text.splitlines()
        for line, _first, rest in _expose_calls(text):
            if not rest:
                snippet = lines[line - 1].strip()
                flag(path, line, "var-help",
                     f"expose() without a HELP description: {snippet}")


# ---- capi-gil ------------------------------------------------------------

def _extern_c_spans(text: str) -> list:
    spans = []
    for m in re.finditer(r'extern\s+"C"\s*\{', text):
        depth, j = 0, m.end() - 1
        while j < len(text):
            if text[j] == "{":
                depth += 1
            elif text[j] == "}":
                depth -= 1
                if depth == 0:
                    break
            j += 1
        spans.append((m.end(), j))
    return spans


def check_capi_bindings() -> None:
    py_text = ""
    for p in sorted((REPO / "brpc_tpu").rglob("*.py")):
        py_text += p.read_text()
    lib_py = REPO / "brpc_tpu" / "rpc" / "_lib.py"
    if "ctypes.CDLL(" not in lib_py.read_text():
        flag(lib_py, 1, "capi-gil",
             "_lib.py must load the runtime via ctypes.CDLL")
    if "PyDLL" in py_text:
        for p in sorted((REPO / "brpc_tpu").rglob("*.py")):
            for i, text in enumerate(p.read_text().splitlines()):
                if "PyDLL" in text:
                    flag(p, i + 1, "capi-gil",
                         "PyDLL holds the GIL across native calls; "
                         "bind through ctypes.CDLL")
    sig = re.compile(
        r"^([A-Za-z_][A-Za-z0-9_ ]*\**)\s*(trpc_[a-z0-9_]+)\s*\(([^)]*)",
        re.M)
    for path in sorted((CPP / "capi").glob("*.cc")):
        text = path.read_text()
        for lo, hi in _extern_c_spans(text):
            body = text[lo:hi]
            for m in sig.finditer(body):
                ret, name, params = (m.group(1).strip(), m.group(2),
                                     m.group(3).strip())
                if f"lib.{name}" not in py_text:
                    continue  # C++-side surface (tools/tests): no binding
                line = text[:lo + m.start()].count("\n") + 1
                wide = ("*" in ret or "int64" in ret or "uint64" in ret
                        or "size_t" in ret)
                if wide and f"lib.{name}.restype" not in py_text:
                    flag(path, line, "capi-gil",
                         f"{name} returns `{ret}` but no Python binding "
                         "sets restype (defaults to 32-bit int)")
                has_params = params not in ("", "void")
                if has_params and f"lib.{name}.argtypes" not in py_text:
                    flag(path, line, "capi-gil",
                         f"{name} takes arguments but no Python binding "
                         "sets argtypes")


# ---- tail-group ----------------------------------------------------------

def check_tail_groups() -> None:
    path = CPP / "net" / "protocol.cc"
    text = path.read_text()

    def groups_in(fn: str) -> list:
        m = re.search(rf"\n\S[^\n]*\b{fn}\(", text)
        if m is None:
            flag(path, 1, "tail-group", f"cannot locate {fn}()")
            return []
        # Function extent: up to the next top-level definition.
        nxt = re.search(r"\n[A-Za-z_][^\n]*\([^\n]*\)\s*\{", text[m.end():])
        body = text[m.start():m.end() + (nxt.start() if nxt else len(text))]
        out = []
        for g in re.finditer(r"//\s*tail-group\s+(\d+)\s*\(([a-z0-9_]+)\)",
                             body):
            out.append((int(g.group(1)), g.group(2)))
        return out

    enc = groups_in("encode_meta")
    dec = groups_in("decode_meta")
    for fn, seq in (("encode_meta", enc), ("decode_meta", dec)):
        ids = [n for n, _ in seq]
        if len(ids) != len(set(ids)):
            flag(path, 1, "tail-group",
                 f"{fn} has duplicate tail-group ids: {ids}")
        if ids != sorted(ids) or (ids and ids != list(range(1, len(ids) + 1))):
            flag(path, 1, "tail-group",
                 f"{fn} tail-group ids not consecutive from 1: {ids}")
    if enc and dec and enc != dec:
        flag(path, 1, "tail-group",
             f"encode/decode tail groups diverge: {enc} vs {dec} — "
             "a one-sided group is a wire break")


# ---- digest-wire ---------------------------------------------------------

def check_digest_wire() -> None:
    cpp_path = CPP / "stat" / "digest.h"
    py_path = REPO / "brpc_tpu" / "rpc" / "observe.py"
    marker = r"digest-wire\s+(\d+)\s*\(([A-Z0-9_]+)\)"

    def table(path: pathlib.Path, comment: str) -> list:
        out = []
        for m in re.finditer(comment + r"\s*" + marker, path.read_text()):
            out.append((int(m.group(1)), m.group(2)))
        return out

    enc = table(cpp_path, r"//")
    # The C++ side documents each format once in digest.h; the Python
    # decoder marks its struct tables.  slo.cc re-states the TRPCFL01
    # marker at the encode site but digest.h owns the canonical table.
    dec = table(py_path, r"#")
    for path, side, seq in ((cpp_path, "encoder", enc),
                            (py_path, "decoder", dec)):
        if not seq:
            flag(path, 1, "digest-wire",
                 f"no digest-wire markers found on the {side} side")
            continue
        ids = sorted(n for n, _ in seq)
        if ids != list(range(1, len(ids) + 1)):
            flag(path, 1, "digest-wire",
                 f"{side} digest-wire ids not unique/consecutive from 1 "
                 f"(append-only table): {ids}")
    if enc and dec and sorted(enc) != sorted(dec):
        flag(cpp_path, 1, "digest-wire",
             f"encoder/decoder digest-wire tables diverge: {sorted(enc)} "
             f"vs {sorted(dec)} — a one-sided layout change corrupts "
             "every fleet merge")


# ---- flag-exists ---------------------------------------------------------

def check_flag_references() -> None:
    # Flags the C++ runtime defines with a literal name — directly
    # (Flag::define_*) or through a defining wrapper (rma.cc int_flag,
    # per-file *_flag helpers), whose idiom is `<something>flag(\n "name"`.
    defined = set()
    defpat = re.compile(
        r'(?:define_(?:bool|int64|double|string)|[a-z_]*flag)\(\s*'
        r'"(trpc_[a-z0-9_]+)"')
    for path in runtime_files():
        for m in defpat.finditer(path.read_text()):
            defined.add(m.group(1))
    # Names minted at runtime from dynamic strings (per-method bounds).
    dynamic_prefixes = ("max_concurrency_",)
    ref = re.compile(r'(?:set_flag|get_flag|trpc_flag_set|trpc_flag_get)'
                     r'\(\s*[bf]?"(trpc_[a-z0-9_]+)"')
    for root in (REPO / "brpc_tpu", REPO / "tools", REPO / "tests"):
        for p in sorted(root.rglob("*.py")):
            text = p.read_text()
            for m in ref.finditer(text):
                name = m.group(1)
                if name in defined or name.startswith(dynamic_prefixes):
                    continue
                line = text[:m.start()].count("\n") + 1
                flag(p, line, "flag-exists",
                     f"flag '{name}' is referenced here but no "
                     "Flag::define_* in cpp/ defines it")


# ---- tuner-rule ----------------------------------------------------------

def _defined_flag_windows() -> dict:
    """{flag_name: define-window text} for every trpc_* flag defined
    with a literal name in cpp/ (directly or via a defining wrapper)."""
    defpat = re.compile(
        r'(?:define_(?:bool|int64|double|string)|[a-z_]*flag)\(\s*'
        r'"(trpc_[a-z0-9_]+)"')
    out = {}
    for path in runtime_files():
        text = path.read_text()
        for m in defpat.finditer(text):
            # The window the flag-validator rule checks: up to 30 lines
            # after the define — set_reloadable(false) there marks the
            # flag immutable.
            tail = text[m.start():]
            out[m.group(1)] = "\n".join(tail.splitlines()[:30])
    return out


def check_tuner_rules() -> None:
    path = CPP / "stat" / "tuner.cc"
    text = path.read_text()
    lines = text.splitlines()
    windows = _defined_flag_windows()

    # Knob assignments must carry a marker naming the SAME literal.
    marker = re.compile(r"//\s*tuner-knob\s*\((trpc_[a-z0-9_]+)\)")
    assign = re.compile(r'\.knob\s*=\s*"(trpc_[a-z0-9_]+)"')
    knobs = []
    for i, ln in enumerate(lines):
        am = assign.search(ln)
        mm = marker.search(ln)
        if am is None and mm is None:
            continue
        if am is None or mm is None or am.group(1) != mm.group(1):
            flag(path, i + 1, "tuner-rule",
                 "rule-table knob assignment and its tuner-knob marker "
                 f"must name the same flag: {ln.strip()}")
            continue
        knobs.append((i + 1, am.group(1)))
    if not knobs:
        flag(path, 1, "tuner-rule",
             "no tuner-knob markers found in the built-in rule table")
    for line, knob in knobs:
        window = windows.get(knob)
        if window is None:
            flag(path, line, "tuner-rule",
                 f"tuner knob '{knob}' is not defined by any "
                 "Flag::define_* in cpp/ — the rule can never actuate")
            continue
        if "set_reloadable(false)" in window:
            flag(path, line, "tuner-rule",
                 f"tuner knob '{knob}' is defined immutable — the "
                 "validated reload path would refuse every actuation")
        # Validated: the flag-validator rule already requires every
        # trpc_* define to install a validator; nothing extra here.

    # Input vars: exposed somewhere in cpp/ WITH a non-empty HELP.
    inputs = []
    inpat = re.compile(r'"([a-z0-9_]+)",\s*//\s*tuner-input')
    for i, ln in enumerate(lines):
        m = inpat.search(ln)
        if m is not None:
            inputs.append((i + 1, m.group(1)))
    if not inputs:
        flag(path, 1, "tuner-rule", "no tuner-input markers found")
    exposes = []
    for p in runtime_files():
        exposes.extend(
            (p, line, first, rest)
            for line, first, rest in _expose_calls(p.read_text()))
    for line, name in inputs:
        hit = False
        for _p, _l, first, rest in exposes:
            lead = first.strip()
            # Exact names expose as the full literal; names ending in
            # '_' are dynamic families — match the prefix with the
            # quote left OPEN so both the `"prefix" + suffix` concat
            # form and a spelled-out `"prefix0"` literal count.
            if not (lead.startswith(f'"{name}"')
                    or (name.endswith("_")
                        and lead.startswith(f'"{name}'))):
                continue
            if re.search(r'"[^"]', rest):  # non-empty HELP string
                hit = True
                break
        if not hit:
            flag(path, line, "tuner-rule",
                 f"tuner input var '{name}' is not exposed with a "
                 "Prometheus HELP description anywhere in cpp/")


# ---- error-code-sync -----------------------------------------------------

def check_error_codes() -> None:
    defpat = re.compile(r"constexpr\s+int\s+(kE[A-Za-z0-9]+)\s*=\s*(\d+)\s*;")
    cpp_codes: dict = {}
    by_value: dict = {}
    for path in runtime_files(exts=(".h",)):
        text = path.read_text()
        for m in defpat.finditer(text):
            name, code = m.group(1), int(m.group(2))
            line = text[:m.start()].count("\n") + 1
            if name in cpp_codes and cpp_codes[name][0] != code:
                flag(path, line, "error-code-sync",
                     f"{name} redefined with a different value "
                     f"({cpp_codes[name][0]} vs {code})")
            cpp_codes[name] = (code, path, line)
            other = by_value.get(code)
            if other is not None and other != name:
                flag(path, line, "error-code-sync",
                     f"{name} and {other} share code {code} — clients "
                     "cannot type the exception")
            by_value[code] = name
    lib_py = REPO / "brpc_tpu" / "rpc" / "_lib.py"
    text = lib_py.read_text()
    block = re.search(r"ERROR_CODES\s*=\s*\{(.*?)\}", text, re.S)
    if block is None:
        flag(lib_py, 1, "error-code-sync",
             "_lib.py must define the ERROR_CODES mirror of the cpp "
             "kE* table")
        return
    py_codes: dict = {}
    for m in re.finditer(r'"(kE[A-Za-z0-9]+)":\s*(\d+)', block.group(1)):
        py_codes[m.group(1)] = int(m.group(2))
    py_line = text[:block.start()].count("\n") + 1
    for name, (code, path, line) in sorted(cpp_codes.items()):
        if name not in py_codes:
            flag(path, line, "error-code-sync",
                 f"{name} ({code}) has no entry in _lib.py ERROR_CODES")
        elif py_codes[name] != code:
            flag(lib_py, py_line, "error-code-sync",
                 f"ERROR_CODES[{name!r}] = {py_codes[name]} but cpp "
                 f"defines {code}")
    for name in sorted(py_codes):
        if name not in cpp_codes:
            flag(lib_py, py_line, "error-code-sync",
                 f"ERROR_CODES entry {name!r} matches no constexpr kE* "
                 "in cpp/")


# ---- atomic-comment ------------------------------------------------------

ATOMIC_FILES = [
    "net/socket.cc", "net/socket.h", "net/messenger.cc", "net/messenger.h",
    "net/qos.cc", "net/qos.h", "net/stripe.cc", "net/stripe.h",
    "net/rma.cc", "net/rma.h", "net/kvstore.cc", "net/kvstore.h",
    "net/lb_hint.h",
]
ATOMIC_RE = re.compile(r"memory_order_(relaxed|acquire)\b")
# "//" inside a string literal ("http://...") is not a comment.
STRING_LIT_RE = re.compile(r'"(?:[^"\\]|\\.)*"')


def check_atomic_comments() -> None:
    for rel in ATOMIC_FILES:
        path = CPP / rel
        lines = path.read_text().splitlines()
        for i, text in enumerate(lines):
            if not ATOMIC_RE.search(text):
                continue
            window = [text] + lines[max(0, i - 4):i]
            if any("//" in STRING_LIT_RE.sub('""', w) for w in window):
                continue
            flag(path, i + 1, "atomic-comment",
                 "relaxed/acquire atomic without a justification comment "
                 "(same line or the 4 lines above): " + text.strip())


def main() -> int:
    check_flag_validators()
    check_var_help()
    check_capi_bindings()
    check_tail_groups()
    check_digest_wire()
    check_flag_references()
    check_tuner_rules()
    check_error_codes()
    check_atomic_comments()
    if violations:
        print(f"lint_trpc: {len(violations)} violation(s)")
        for v in violations:
            print("  " + v)
        return 1
    print("lint_trpc: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
