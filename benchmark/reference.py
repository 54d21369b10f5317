"""The plain reference: what ``POST /parse`` must answer, in plain Python.

A copy of the reference semantics (the JVM's AnalysisService,
ScoringService, ContextAnalysisService and FrequencyTrackingService, as
``log_parser_tpu/golden/engine.py`` states them), kept here so that no
change to the program can change the yardstick. It imports nothing of
the program and takes nothing the program made: it reads the pattern
YAML the server was given and makes each request again from its id.

- lines: Java's ``split("\\r?\\n")`` (trailing empty lines dropped);
- match: ``Matcher.find`` per line and pattern, in discovery order
  (line-major, then set order, then pattern order). One plain speed-up:
  where every top-level alternative of a regex holds a run of literal
  characters that any match must contain, only lines that contain one
  of those runs are searched (``required_literals``; a test holds the
  gated matcher equal to the ungated one);
- score: confidence × severity × chronological × proximity × temporal ×
  context × (1 − frequency penalty), left to right, with the penalty read
  before the match is recorded and the counts carried across requests in
  the order the server finalized them;
- context: lines before, the matched line and lines after, per the
  pattern's ``context_extraction``.

``float32=True`` computes every factor and product in float32 instead
of float64: the control, which the comparison must refuse.
"""

from __future__ import annotations

import bisect
import hashlib
import json
import math
import os
import re

import numpy as np
import yaml

# ----------------------------------------------------------- Java dialect

_LINE_SEP = re.compile(r"\r?\n")
_POSIX_MAP = {
    "Alpha": "a-zA-Z",
    "Digit": "0-9",
    "Alnum": "a-zA-Z0-9",
    "Upper": "A-Z",
    "Lower": "a-z",
    "Space": r" \t\n\x0b\f\r",
    "Punct": r"!-/:-@\[-`{-~",
    "XDigit": "0-9a-fA-F",
}
_POSIX_RE = re.compile(r"\\([pP])\{(\w+)\}")
_NAMED_GROUP_RE = re.compile(r"\(\?<([A-Za-z][A-Za-z0-9]*)>")
_NAMED_BACKREF_RE = re.compile(r"\\k<([A-Za-z][A-Za-z0-9]*)>")
_BRACE_QUANT_RE = re.compile(r"\{\d+(?:,\d*)?\}")
_INLINE_FLAGS_RE = re.compile(r"\(\?[a-zA-Z-]+\)")


def java_split_lines(logs: str) -> list[str]:
    parts = _LINE_SEP.split(logs)
    if len(parts) == 1:
        return parts
    while parts and parts[-1] == "":
        parts.pop()
    return parts


def translate_java_regex(pattern: str) -> str:
    """Java regex → Python ``re``; ``ValueError`` where the semantics
    cannot be kept (possessive quantifiers, atomic groups, class
    intersections, mid-pattern inline flags, unknown ``\\p`` classes)."""
    out: list[str] = []
    i, n = 0, len(pattern)
    in_class = False

    def fail(what: str) -> ValueError:
        return ValueError(f"unsupported Java regex construct ({what}) in {pattern!r}")

    while i < n:
        c = pattern[i]
        if c == "\\":
            m = _POSIX_RE.match(pattern, i)
            if m:
                negated, name = m.group(1) == "P", m.group(2)
                if name not in _POSIX_MAP:
                    raise fail(f"\\p{{{name}}}")
                content = _POSIX_MAP[name]
                if in_class:
                    if negated:
                        raise fail("\\P inside character class")
                    out.append(content)
                else:
                    out.append(("[^" if negated else "[") + content + "]")
                i = m.end()
                continue
            m = _NAMED_BACKREF_RE.match(pattern, i)
            if m:
                out.append(f"(?P={m.group(1)})")
                i = m.end()
                continue
            nxt = pattern[i + 1] if i + 1 < n else ""
            if not in_class:
                if nxt == "z":
                    out.append(r"\Z")
                    i += 2
                    continue
                if nxt == "Z":
                    out.append(r"(?=\r?\Z)")
                    i += 2
                    continue
                if nxt == "Q":
                    end = pattern.find("\\E", i + 2)
                    content = pattern[i + 2 : end if end >= 0 else n]
                    escaped = re.escape(content)
                    if escaped and escaped[0].isdigit():
                        escaped = f"\\x{ord(escaped[0]):02x}" + escaped[1:]
                    out.append(escaped)
                    i = (end + 2) if end >= 0 else n
                    continue
            out.append(pattern[i : i + 2])
            i += 2
            continue
        if in_class:
            if c == "]":
                in_class = False
            elif c == "[":
                raise fail("nested character class")
            elif c == "&" and pattern.startswith("&&", i):
                raise fail("class intersection &&")
            out.append(c)
            i += 1
            continue
        if c == "[":
            in_class = True
            out.append(c)
            i += 1
            if i < n and pattern[i] == "^":
                out.append("^")
                i += 1
            continue
        if c == ".":
            out.append("[^\n\r\x85\u2028\u2029]")
            i += 1
            continue
        if c == "$":
            out.append(r"(?=\r?\Z)")
            i += 1
            continue
        if c == "(":
            if pattern.startswith("(?>", i):
                raise fail("atomic group")
            m = _NAMED_GROUP_RE.match(pattern, i)
            if m:
                out.append(f"(?P<{m.group(1)}>")
                i = m.end()
                continue
            m = _INLINE_FLAGS_RE.match(pattern, i)
            if m and i > 0:
                raise fail(f"mid-pattern inline flags {m.group(0)}")
            out.append(c)
            i += 1
            continue
        if c in "*+?":
            out.append(c)
            i += 1
            if i < n and pattern[i] == "+":
                raise fail("possessive quantifier")
            if i < n and pattern[i] == "?":
                out.append("?")
                i += 1
            continue
        if c == "{":
            m = _BRACE_QUANT_RE.match(pattern, i)
            if m:
                out.append(m.group(0))
                i = m.end()
                if i < n and pattern[i] == "+":
                    raise fail("possessive quantifier")
                continue
            out.append(c)
            i += 1
            continue
        out.append(c)
        i += 1
    return "".join(out)


def compile_java_regex(pattern: str, case_insensitive: bool = False) -> re.Pattern:
    flags = re.ASCII | (re.IGNORECASE if case_insensitive else 0)
    return re.compile(translate_java_regex(pattern), flags)


# ------------------------------------------------------------ literal gate

_CLASS_ESCAPES = set("dDsSwWbBAGZztnrfae")


def _skip_class(s: str, i: int) -> int | None:
    """Index after the character class opening at ``s[i] == '['``."""
    j = i + 1
    while j < len(s):
        c = s[j]
        if c == "\\":
            j += 2
            continue
        if c == "[":
            return None
        if c == "]":
            return j + 1
        j += 1
    return None


def _skip_group(s: str, i: int) -> int | None:
    """Index after the group opening at ``s[i] == '('``."""
    depth, j = 0, i
    while j < len(s):
        c = s[j]
        if c == "\\":
            j += 2
            continue
        if c == "[":
            j = _skip_class(s, j)
            if j is None:
                return None
            continue
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return j + 1
        j += 1
    return None


def _split_top(s: str) -> list[str] | None:
    parts, start, j = [], 0, 0
    while j < len(s):
        c = s[j]
        if c == "\\":
            j += 2
            continue
        if c == "[":
            j = _skip_class(s, j)
            if j is None:
                return None
            continue
        if c == "(":
            j = _skip_group(s, j)
            if j is None:
                return None
            continue
        if c == "|":
            parts.append(s[start:j])
            start = j + 1
        j += 1
    parts.append(s[start:])
    return parts


def _longest_literal(s: str) -> str | None:
    """The longest run of characters that every match of ``s`` (one
    top-level alternative) contains, or None where a construct is not
    understood. Groups, classes, anchors and escapes end a run; a
    character made optional by ``?``, ``*`` or ``{..}`` leaves it."""
    best, run = "", []

    def flush():
        nonlocal best, run
        if len(run) > len(best):
            best = "".join(run)
        run = []

    i, n = 0, len(s)
    while i < n:
        c = s[i]
        if c == "\\":
            nxt = s[i + 1] if i + 1 < n else ""
            if nxt == "" or (nxt.isalnum() and nxt not in _CLASS_ESCAPES
                             and nxt not in "pP"):
                return None
            if nxt in "pP":
                end = s.find("}", i)
                if end < 0:
                    return None
                flush()
                i = end + 1
            elif nxt.isalnum():
                flush()
                i += 2
            else:
                run.append(nxt)
                i += 2
            continue
        if c == "[":
            flush()
            j = _skip_class(s, i)
            if j is None:
                return None
            i = j
            continue
        if c == "(":
            flush()
            j = _skip_group(s, i)
            if j is None:
                return None
            i = j
            continue
        if c in "*?" or (c == "{" and _BRACE_QUANT_RE.match(s, i)):
            if run:
                run.pop()
            flush()
            m = _BRACE_QUANT_RE.match(s, i) if c == "{" else None
            i = m.end() if m else i + 1
            while i < n and s[i] in "?+":
                i += 1
            continue
        if c == "+":
            flush()
            i += 1
            while i < n and s[i] in "?+":
                i += 1
            continue
        if c in ".^${}])|":
            flush()
            i += 1
            continue
        run.append(c)
        i += 1
    flush()
    return best


def required_literals(regex: str, min_len: int = 3) -> list[str] | None:
    """One literal per top-level alternative that any match contains, or
    None when some alternative has none of ``min_len`` characters."""
    if _INLINE_FLAGS_RE.match(regex) or regex.startswith("(?"):
        return None
    alts = _split_top(regex)
    if alts is None:
        return None
    lits = []
    for alt in alts:
        lit = _longest_literal(alt)
        if lit is None or len(lit) < min_len:
            return None
        lits.append(lit)
    return sorted(set(lits))


# ---------------------------------------------------------------- library

SEVERITY_MULTIPLIERS = {"CRITICAL": 5.0, "HIGH": 3.0, "MEDIUM": 2.0, "LOW": 1.5, "INFO": 1.0}
VALID_SEVERITIES = frozenset(SEVERITY_MULTIPLIERS)
ERROR_PATTERN = compile_java_regex(r"\b(ERROR|FATAL|CRITICAL|SEVERE)\b", case_insensitive=True)
WARN_PATTERN = compile_java_regex(r"\b(WARN|WARNING)\b", case_insensitive=True)
STACK_TRACE_PATTERN = compile_java_regex(r"^\s*at\s+[\w\.\$]+\(.*\)\s*$")
EXCEPTION_PATTERN = compile_java_regex(r"\b\w*Exception\b|\b\w*Error\b")
SEQUENCE_NEAR_WINDOW = 5

_CAMEL = re.compile(r"(?<!^)(?=[A-Z])")


def _snake(d):
    if isinstance(d, dict):
        return {_CAMEL.sub("_", k).lower(): _snake(v) for k, v in d.items()}
    if isinstance(d, list):
        return [_snake(v) for v in d]
    return d


def _yaml_files(directory: str) -> list[str]:
    out = []
    for root, _dirs, files in sorted(os.walk(directory)):
        for name in sorted(files):
            if name.endswith((".yml", ".yaml")):
                path = os.path.join(root, name)
                if os.path.isfile(path):
                    out.append(path)
    return out


def _valid_set(data: dict) -> bool:
    seen = set()
    for p in data.get("patterns") or []:
        pid = p.get("id") or ""
        if pid and pid in seen:
            return False
        seen.add(pid)
        sev = p.get("severity")
        if sev and str(sev).upper() not in VALID_SEVERITIES:
            return False
    return True


class Library:
    """The patterns of a YAML directory, compiled, in discovery order."""

    def __init__(self, directory: str):
        loader = getattr(yaml, "CSafeLoader", yaml.SafeLoader)
        self.compiled: dict[str, re.Pattern] = {}
        self.primaries: list[dict] = []
        self.skipped: list[str] = []
        for path in _yaml_files(directory):
            try:
                with open(path, encoding="utf-8") as f:
                    data = yaml.load(f, Loader=loader)
            except (OSError, yaml.YAMLError):
                continue
            if not isinstance(data, dict):
                continue
            data = _snake(data)
            if not _valid_set(data):
                continue
            for p in data.get("patterns") or []:
                self._add(p)

    def regex(self, r: str) -> re.Pattern:
        pat = self.compiled.get(r)
        if pat is None:
            pat = compile_java_regex(r)
            self.compiled[r] = pat
        return pat

    def _add(self, p: dict) -> None:
        prim = p.get("primary_pattern")
        try:
            if prim is not None:
                compiled = self.regex(prim.get("regex") or "")
            for sec in p.get("secondary_patterns") or []:
                self.regex(sec.get("regex") or "")
            for seq in p.get("sequence_patterns") or []:
                for ev in seq.get("events") or []:
                    self.regex(ev.get("regex") or "")
        except (ValueError, re.error):
            self.skipped.append(p.get("id") or "")
            return
        if prim is None:
            return
        ctx = p.get("context_extraction")
        regex = prim.get("regex") or ""
        self.primaries.append({
            "id": p.get("id") or "",
            "severity": p.get("severity") or "",
            "confidence": float(prim.get("confidence") or 0.0),
            "compiled": compiled,
            "gate": required_literals(regex),
            "secondaries": [
                (s.get("regex") or "", float(s.get("weight") or 0.0),
                 int(s.get("proximity_window") or 0))
                for s in p.get("secondary_patterns") or []
            ],
            "sequences": [
                (float(s.get("bonus_multiplier") or 0.0),
                 [e.get("regex") or "" for e in s.get("events") or []])
                for s in p.get("sequence_patterns") or []
            ],
            "context": None if ctx is None else (
                int(ctx.get("lines_before") or 0), int(ctx.get("lines_after") or 0)
            ),
        })


# ------------------------------------------------------------------ match

def match_plain(lib: Library, lines: list[str]) -> list[tuple[int, int]]:
    """(line index, pattern index) of every match, one search per line
    and pattern, in discovery order."""
    return [
        (li, pi)
        for li, line in enumerate(lines)
        for pi, p in enumerate(lib.primaries)
        if p["compiled"].search(line)
    ]


class Request:
    """One request's lines, and for each regex the sorted indices of the
    lines it finds a match in, each searched once: what the scoring's
    window and backward searches look up. Where a regex has required
    literals, only lines that contain one are searched."""

    def __init__(self, lib: Library, lines: list[str]):
        self.lib = lib
        self.lines = lines
        self.text = "\n".join(lines)
        self.starts, pos = [], 0
        for line in lines:
            self.starts.append(pos)
            pos += len(line) + 1
        self._hits: dict[str, list[int]] = {}

    def _candidates(self, gate: list[str] | None):
        if gate is None:
            return range(len(self.lines))
        found = set()
        text, starts = self.text, self.starts
        for lit in gate:
            at = text.find(lit)
            while at >= 0:
                li = bisect.bisect_right(starts, at) - 1
                found.add(li)
                nxt = starts[li + 1] if li + 1 < len(starts) else len(text)
                at = text.find(lit, nxt)
        return sorted(found)

    def search(self, compiled: re.Pattern, gate: list[str] | None) -> list[int]:
        lines = self.lines
        return [li for li in self._candidates(gate) if compiled.search(lines[li])]

    def hits(self, regex: str) -> list[int]:
        h = self._hits.get(regex)
        if h is None:
            h = self.search(self.lib.regex(regex), required_literals(regex))
            self._hits[regex] = h
        return h


def match(lib: Library, lines: list[str], req: Request | None = None
          ) -> list[tuple[int, int]]:
    """``match_plain``'s answer, searching only lines that hold a
    pattern's required literal where it has one."""
    req = req or Request(lib, lines)
    hits: dict[int, list[int]] = {}
    for pi, p in enumerate(lib.primaries):
        for li in req.search(p["compiled"], p["gate"]):
            hits.setdefault(li, []).append(pi)
    return [(li, pi) for li in sorted(hits) for pi in sorted(hits[li])]


# ------------------------------------------------------------------ score

def context_digest(before, matched, after) -> str:
    return hashlib.sha1(
        json.dumps([before, matched, after]).encode("utf-8", "surrogatepass")
    ).hexdigest()


class Scorer:
    """The frequency-free factors of one event, in float64 or float32."""

    def __init__(self, lib: Library, scoring: dict, float32: bool = False):
        self.lib = lib
        self.s = scoring
        self.f = np.float32 if float32 else float
        self.exp = (lambda x: np.exp(np.float32(x))) if float32 else math.exp

    def _div(self, a, b):
        f = self.f
        if b == 0:
            if a == 0 or a != a:
                return f(math.nan)
            return f(math.copysign(math.inf, a) * math.copysign(1.0, b))
        return f(a) / f(b)

    def chronological(self, idx: int, n: int):
        f, s = self.f, self.s
        pos = f(idx) / f(n)
        early = f(s["chronological_early_bonus_threshold"])
        pen = f(s["chronological_penalty_threshold"])
        if pos <= early:
            rng = f(s["chronological_max_early_bonus"]) - f(1.5)
            return f(1.5) + (early - pos) * self._div(rng, early)
        if pos <= pen:
            return f(1.0) + (pen - pos) * self._div(f(0.5), pen - early)
        return f(0.5) + (f(1.0) - pos)

    def proximity(self, p: dict, idx: int, req: Request):
        f = self.f
        total = f(0.0)
        if not p["secondaries"]:
            return f(1.0)
        for regex, weight, window in p["secondaries"]:
            w = min(int(self.s["proximity_max_window"]), window)
            lo, hi = max(0, idx - w), min(len(req.lines), idx + w + 1)
            h = req.hits(regex)
            closest = -1.0
            k = bisect.bisect_left(h, idx)  # h[k-1] < idx <= h[k]
            if k > 0 and h[k - 1] >= lo:
                closest = float(idx - h[k - 1])
            k = bisect.bisect_right(h, idx)  # first hit after idx
            if k < len(h) and h[k] < hi:
                d = float(h[k] - idx)
                if closest < 0 or d < closest:
                    closest = d
            if closest >= 0:
                total = total + f(weight) * self.exp(
                    -f(closest) / f(self.s["proximity_decay_constant"])
                )
        return f(1.0) + total

    def temporal(self, p: dict, idx: int, req: Request):
        f = self.f
        if not p["sequences"]:
            return f(1.0)
        total = f(0.0)
        for bonus, events in p["sequences"]:
            if self._sequence(events, idx, req):
                total = total + f(bonus)
        return f(1.0) + total

    @staticmethod
    def _sequence(events: list[str], idx: int, req: Request) -> bool:
        """The last event within ±5 lines of the primary; each earlier
        one at its nearest line before the one after it, searching back
        from the primary for the second-to-last."""
        if not events:
            return False
        current = 0
        for i in range(len(events) - 1, -1, -1):
            h = req.hits(events[i])
            if i == len(events) - 1:
                lo = max(0, idx - SEQUENCE_NEAR_WINDOW)
                hi = min(len(req.lines), idx + SEQUENCE_NEAR_WINDOW + 1)
                k = bisect.bisect_left(h, lo)
                if not (k < len(h) and h[k] < hi):
                    return False
                current = idx
            else:
                k = bisect.bisect_left(h, current) - 1
                if k < 0:
                    return False
                current = h[k]
        return True

    def context(self, all_lines: list[str]):
        f = self.f
        score = f(0.0)
        err = stack = 0
        for line in all_lines:
            if ERROR_PATTERN.search(line):
                err += 1
                score = score + f(0.4)
            elif WARN_PATTERN.search(line):
                score = score + f(0.2)
            if STACK_TRACE_PATTERN.search(line):
                stack += 1
                score = score + f(0.1)
            if EXCEPTION_PATTERN.search(line):
                score = score + f(0.3)
        if stack > 0:
            score = score + min(f(stack) * f(0.1), f(0.5))
        total = len(all_lines)
        if total > 10 and (stack + err) > total * 0.7:
            score = score * f(0.8)
        return min(f(1.0) + score, f(self.s["context_max_context_factor"]))

    def event(self, req: Request, li: int, pi: int):
        """(pattern id, the product of the six frequency-free factors,
        context digest) of the match of pattern ``pi`` on line ``li``."""
        lines = req.lines
        p = self.lib.primaries[pi]
        f = self.f
        ctx = p["context"]
        if ctx is None:
            before = after = None
            all_lines = [lines[li]]
        else:
            before = lines[max(0, li - ctx[0]):li]
            after = lines[li + 1:min(len(lines), li + 1 + ctx[1])]
            all_lines = before + [lines[li]] + after
        base = (
            f(p["confidence"])
            * f(SEVERITY_MULTIPLIERS.get(p["severity"].upper(), 1.0))
            * self.chronological(li, len(lines))
            * self.proximity(p, li, req)
            * self.temporal(p, li, req)
            * self.context(all_lines)
        )
        return p["id"], base, context_digest(before, lines[li], after)


class Frequency:
    """Per-pattern-id match counts carried across requests; the window
    is an hour, longer than any run, so nothing expires."""

    def __init__(self, scoring: dict, float32: bool = False):
        self.f = np.float32 if float32 else float
        self.s = scoring
        self.counts: dict[str, int] = {}

    def score(self, pid: str, base):
        f, s = self.f, self.s
        penalty = f(0.0)
        if pid.strip() and pid in self.counts:
            rate = f(self.counts[pid]) / (f(s["frequency_time_window_hours"] * 3600.0) / f(3600.0))
            thr = f(s["frequency_threshold"])
            if not rate <= thr:
                excess = rate - thr
                penalty = min(f(s["frequency_max_penalty"]), excess / thr)
        if pid.strip():
            self.counts[pid] = self.counts.get(pid, 0) + 1
        return float(base * (f(1.0) - penalty))


def analyze(lib: Library, scorer: Scorer, logs: str) -> tuple[int, list]:
    """(line count, [(line number, pattern id, factors, context digest)])
    of one request, everything but the frequency penalty."""
    lines = java_split_lines(logs)
    req = Request(lib, lines)
    return len(lines), [
        (li + 1, *scorer.event(req, li, pi)) for li, pi in match(lib, lines, req)
    ]
