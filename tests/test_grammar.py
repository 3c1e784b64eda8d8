"""JSON grammar-constrained decoding: automaton, vocab composer, device parity.

The property under test: ANY token sequence that stays inside the mask and
ends at EOS decodes to valid JSON (json.loads succeeds) — over random
rollouts with a vocab that mixes single-byte and multi-byte tokens.
"""

import json

import numpy as np
import pytest

from dynamo_tpu.engine.grammar import (
    AFTER_VALUE, DEAD, INIT_STATE, JsonGrammar, MAX_DEPTH, VocabTables,
    compile_vocab, device_tables, grammar_mask,
    token_bytes_map,
)

EOS = 0


def make_vocab():
    """Token 0 = EOS (special); 1..256 = single bytes; then multi-byte."""
    toks: list = [None]
    for b in range(256):
        toks.append(bytes([b]))
    multi = [b'{"', b'":', b'", "', b'"}', b'true', b'false', b'null',
             b'123', b'3.14', b'-1e9', b'[1,', b'{}', b'[]', b'  ',
             b'\\"', b'\\u00ff', b'}}', b']]', b'"a"', b'0.5]',
             b'},', b'],', b',"', b'{"a":', b'[[', b'{{']
    toks.extend(multi)
    return toks


@pytest.fixture(scope="module")
def tables() -> VocabTables:
    return compile_vocab(make_vocab(), eos_ids=[EOS])


def tok_id(toks, b: bytes) -> int:
    return toks.index(b)


def decode_ids(toks, ids) -> bytes:
    return b"".join(toks[i] for i in ids if i != EOS and toks[i])


def test_rollouts_always_valid_json(tables):
    toks = make_vocab()
    rng = np.random.default_rng(0)
    n_done = 0
    for trial in range(200):
        s, d, st = INIT_STATE, 0, 0
        ids = []
        for _ in range(120):
            mask = tables.valid_mask(s, d, st)
            valid = np.flatnonzero(mask)
            assert valid.size > 0, f"dead end at state {s} depth {d}"
            t = int(rng.choice(valid))
            ids.append(t)
            if t == EOS:
                break
            s, d, st = tables.advance(s, d, st, t)
        if ids and ids[-1] == EOS:
            n_done += 1
            # the automaton is byte-level: lone 0x80+ bytes are legal JSON
            # string *bytes*; substitute them for the utf-8 parse check
            text = decode_ids(toks, ids).decode("utf-8", errors="replace")
            assert json.loads(text) is not None or text.strip() in ("null",), text
    assert n_done >= 50  # most random walks must terminate


def test_greedy_style_rollout_objects(tables):
    """Bias rollouts toward structure tokens so nesting gets exercised."""
    toks = make_vocab()
    rng = np.random.default_rng(1)
    prefer = [tok_id(toks, b) for b in
              (b'{"', b'":', b'"}', b'[1,', b'123', b'"a"', b'{', b'}',
               b'[', b']', b'"', b':', b',', b'true')]
    deep_seen = 0
    for trial in range(300):
        s, d, st = INIT_STATE, 0, 0
        ids = []
        for _ in range(200):
            mask = tables.valid_mask(s, d, st)
            cand = [p for p in prefer if mask[p]]
            if cand and rng.random() < 0.7:
                t = int(rng.choice(cand))
            else:
                valid = np.flatnonzero(mask)
                t = int(rng.choice(valid))
            ids.append(t)
            if t == EOS:
                break
            s, d, st = tables.advance(s, d, st, t)
            deep_seen = max(deep_seen, d)
        if ids and ids[-1] == EOS:
            text = decode_ids(toks, ids).decode("utf-8", errors="replace")
            json.loads(text)
    assert deep_seen >= 3  # nesting actually exercised


def test_structural_masks(tables):
    toks = make_vocab()
    s, d, st = INIT_STATE, 0, 0
    m = tables.valid_mask(s, d, st)
    # value starts allowed, EOS not, ':' not, '}' not
    assert m[tok_id(toks, b'{')] and m[tok_id(toks, b'[')] and m[tok_id(toks, b'"')]
    assert not m[EOS] and not m[tok_id(toks, b':')] and not m[tok_id(toks, b'}')]
    # after '{': key or '}' only — no value starts, no ','
    s, d, st = tables.advance(s, d, st, tok_id(toks, b'{'))
    m = tables.valid_mask(s, d, st)
    assert m[tok_id(toks, b'"')] and m[tok_id(toks, b'}')]
    assert not m[tok_id(toks, b'[')] and not m[tok_id(toks, b',')]
    assert not m[tok_id(toks, b']')]  # wrong closer for OBJ
    # close it: complete JSON -> EOS only
    s, d, st = tables.advance(s, d, st, tok_id(toks, b'}'))
    m = tables.valid_mask(s, d, st)
    assert m[EOS]
    assert m.sum() == 1  # nothing but EOS after a complete value


def test_bracket_matching_through_stack(tables):
    toks = make_vocab()
    # [[ then {} then ]] — the ']]' multi-pop must check both stack levels
    s, d, st = INIT_STATE, 0, 0
    for b in (b'[', b'['):
        s, d, st = tables.advance(s, d, st, tok_id(toks, b))
    assert d == 2
    m = tables.valid_mask(s, d, st)
    assert m[tok_id(toks, b']]')] is not None
    # '}}' must be invalid here (stack holds ARR, ARR)
    assert not m[tok_id(toks, b'}}')]
    s2, d2, st2 = tables.advance(s, d, st, tok_id(toks, b'1'))
    m = tables.valid_mask(s2, d2, st2)
    assert m[tok_id(toks, b']]')]
    s3, d3, st3 = tables.advance(s2, d2, st2, tok_id(toks, b']]'))
    assert d3 == 0
    m = tables.valid_mask(s3, d3, st3)
    assert m[EOS] and m.sum() == 1


def test_context_dependent_tokens_are_conservative(tables):
    toks = make_vocab()
    # '},' — comma after popping into unknown context: masked from every
    # value-position state (it stays valid inside strings, where it is
    # plain content)
    jid = tok_id(toks, b'},')
    for c in ("T", "O", "A"):
        assert tables.next_state[AFTER_VALUE[c], jid] == DEAD
    # but the same chars as two tokens work: {"a": {} , ...
    s, d, st = INIT_STATE, 0, 0
    for b in (b'{"a":', b'{'):
        s, d, st = tables.advance(s, d, st, tok_id(toks, b))
    m = tables.valid_mask(s, d, st)
    assert m[tok_id(toks, b'}')]
    s, d, st = tables.advance(s, d, st, tok_id(toks, b'}'))
    m = tables.valid_mask(s, d, st)
    assert m[tok_id(toks, b',')] and m[tok_id(toks, b'}')]
    assert not m[tok_id(toks, b']')]


def test_string_escapes_and_numbers(tables):
    toks = make_vocab()
    seq = [b'[', b'"', b'\\"', b'a', b'"', b',', b'-1e9', b']']
    s, d, st = INIT_STATE, 0, 0
    for b in seq:
        t = tok_id(toks, b)
        assert tables.valid_mask(s, d, st)[t], f"{b} rejected"
        s, d, st = tables.advance(s, d, st, t)
    m = tables.valid_mask(s, d, st)
    assert m[EOS]
    text = b''.join(seq).decode()
    json.loads(text)


def test_number_cannot_be_malformed(tables):
    toks = make_vocab()
    s, d, st = INIT_STATE, 0, 0
    s, d, st = tables.advance(s, d, st, tok_id(toks, b'-'))
    m = tables.valid_mask(s, d, st)
    assert not m[EOS] and not m[tok_id(toks, b'-')] and not m[tok_id(toks, b'.')]
    assert m[tok_id(toks, b'0')]
    s, d, st = tables.advance(s, d, st, tok_id(toks, b'0'))
    m = tables.valid_mask(s, d, st)
    # leading zero: no second digit
    assert not m[tok_id(toks, b'0')] and not m[tok_id(toks, b'7')]
    assert m[tok_id(toks, b'.')] and m[EOS]


def test_depth_limit(tables):
    toks = make_vocab()
    s, d, st = INIT_STATE, 0, 0
    for _ in range(MAX_DEPTH):
        t = tok_id(toks, b'[')
        assert tables.valid_mask(s, d, st)[t]
        s, d, st = tables.advance(s, d, st, t)
    m = tables.valid_mask(s, d, st)
    assert not m[tok_id(toks, b'[')] and not m[tok_id(toks, b'{')]
    assert m[tok_id(toks, b'1')] and m[tok_id(toks, b']')]


def test_device_matches_host(tables):
    """grammar_mask (jnp) == valid_mask (numpy) along random constrained
    walks, each advanced by the host's ``advance``."""
    import jax.numpy as jnp

    toks = make_vocab()
    gt = device_tables(tables)
    rng = np.random.default_rng(7)
    B = 4
    s = np.full(B, INIT_STATE, np.int32)
    d = np.zeros(B, np.int32)
    st = np.zeros(B, np.int32)
    jrows = np.ones(B, bool)
    v = tables.vocab_size
    for step in range(40):
        logits = rng.normal(size=(B, v)).astype(np.float32)
        masked = np.asarray(grammar_mask(
            jnp.asarray(logits), gt, jnp.asarray(jrows), jnp.asarray(s),
            jnp.asarray(d), jnp.asarray(st)))
        picks = np.zeros(B, np.int32)
        for i in range(B):
            host_ok = tables.valid_mask(int(s[i]), int(d[i]), int(st[i]))
            dev_ok = masked[i] > -1e29
            np.testing.assert_array_equal(dev_ok, host_ok,
                                          err_msg=f"row {i} step {step}")
            choices = np.flatnonzero(host_ok & (np.arange(v) != EOS))
            picks[i] = int(rng.choice(choices)) if choices.size else EOS
        for i in range(B):
            s[i], d[i], st[i] = tables.advance(
                int(s[i]), int(d[i]), int(st[i]), int(picks[i]))


def test_token_bytes_map_byte_level():
    class FakeTk:
        def get_vocab(self):
            return {"Ġhello": 0, "{": 1, "<|eot|>": 2, "ĊĊ": 3}

        def get_added_tokens_decoder(self):
            return {}

    out = token_bytes_map(FakeTk())
    assert out[0] == b" hello"
    assert out[1] == b"{"
    assert out[2] is None  # <...> treated as special
    assert out[3] == b"\n\n"


def test_token_bytes_map_sentencepiece():
    class FakeTk:
        def get_vocab(self):
            return {"▁the": 0, "<0x0A>": 1, "a": 2, "<s>": 3}

        def get_added_tokens_decoder(self):
            return {}

    out = token_bytes_map(FakeTk())
    assert out[0] == b" the"
    assert out[1] == b"\n"
    assert out[2] == b"a"
    assert out[3] is None


def test_parse_request_response_format():
    from dynamo_tpu.llm.openai import OpenAIError, parse_request

    base = {"model": "m", "messages": [{"role": "user", "content": "hi"}]}
    req = parse_request({**base, "response_format": {"type": "json_object"}},
                        chat=True)
    assert req.response_format == "json_object"
    assert req.sampling.json_mode

    req = parse_request({**base, "response_format": {"type": "text"}}, chat=True)
    assert req.response_format is None and not req.sampling.json_mode

    req = parse_request(
        {**base, "response_format": {
            "type": "json_schema",
            "json_schema": {"name": "x", "schema": {"type": "object"}}}},
        chat=True)
    assert req.response_format == "json_schema"
    assert req.json_schema["schema"] == {"type": "object"}
    assert req.sampling.json_mode

    import pytest as _pytest
    with _pytest.raises(OpenAIError):
        parse_request({**base, "response_format": {"type": "yaml"}}, chat=True)
    with _pytest.raises(OpenAIError):
        parse_request({**base, "response_format": {"type": "json_schema"}},
                      chat=True)


def test_parse_request_response_format_completions():
    from dynamo_tpu.llm.openai import OpenAIError, parse_request

    base = {"model": "m", "prompt": "say json"}
    # json_object is endpoint-agnostic
    req = parse_request({**base, "response_format": {"type": "json_object"}},
                        chat=False)
    assert req.sampling.json_mode
    # json_schema needs a chat transcript for schema injection
    import pytest as _pytest
    with _pytest.raises(OpenAIError):
        parse_request(
            {**base, "response_format": {
                "type": "json_schema",
                "json_schema": {"name": "x", "schema": {}}}},
            chat=False)


def test_choice_grammar_masks_to_choices(tables):
    from dynamo_tpu.engine.grammar import compile_choice_vocab

    toks = make_vocab()
    ct = compile_choice_vocab(toks, ["yes", "no", "nope"], eos_ids=[EOS])
    s, d, st = 1, 0, 0  # root
    m = ct.valid_mask(s, d, st)
    assert m[tok_id(toks, b"y")] and m[tok_id(toks, b"n")]
    assert not m[tok_id(toks, b"x")] and not m[EOS]
    # walk "n" -> "o": complete choice "no" but also prefix of "nope"
    s, d, st = ct.advance(s, d, st, tok_id(toks, b"n"))
    s, d, st = ct.advance(s, d, st, tok_id(toks, b"o"))
    m = ct.valid_mask(s, d, st)
    assert m[EOS] and m[tok_id(toks, b"p")]
    # complete "nope": terminal, EOS only
    s, d, st = ct.advance(s, d, st, tok_id(toks, b"p"))
    s, d, st = ct.advance(s, d, st, tok_id(toks, b"e"))
    m = ct.valid_mask(s, d, st)
    assert m[EOS] and m.sum() == 1
    # multi-byte vocab tokens compose: "true" is not a choice here
    assert not ct.valid_mask(1, 0, 0)[tok_id(toks, b"true")]


def test_choice_grammar_rollout_terminates(tables):
    import numpy as _np

    from dynamo_tpu.engine.grammar import compile_choice_vocab

    toks = make_vocab()
    choices = ["alpha", "beta", "true"]  # 'true' is a single vocab token
    ct = compile_choice_vocab(toks, choices, eos_ids=[EOS])
    rng = _np.random.default_rng(3)
    for _ in range(30):
        s, d, st = 1, 0, 0
        out = []
        for _ in range(20):
            m = ct.valid_mask(s, d, st)
            t = int(rng.choice(_np.flatnonzero(m)))
            if t == EOS:
                break
            out.append(t)
            s, d, st = ct.advance(s, d, st, t)
        text = decode_ids(toks, out).decode()
        assert text in choices, text


def test_compose_tables_offsets(tables):
    from dynamo_tpu.engine.grammar import (
        compile_choice_vocab, compose_tables,
    )

    toks = make_vocab()
    c1 = compile_choice_vocab(toks, ["on", "off"], eos_ids=[EOS])
    comp, offs = compose_tables([tables, c1])
    assert offs[0] == 0 and offs[1] == tables.n_states
    # JSON rows behave identically at offset 0
    import numpy as _np

    _np.testing.assert_array_equal(comp.valid_mask(1, 0, 0),
                                   tables.valid_mask(1, 0, 0))
    # choice rows behave identically at their offset
    root = offs[1] + 1
    m = comp.valid_mask(root, 0, 0)
    _np.testing.assert_array_equal(m, c1.valid_mask(1, 0, 0))
    # walking 'o' in the composite lands at a shifted state with the
    # same continuations
    s, d, st = comp.advance(root, 0, 0, tok_id(toks, b"o"))
    assert s > offs[1]
    m2 = comp.valid_mask(s, d, st)
    assert m2[tok_id(toks, b"n")] and m2[tok_id(toks, b"f")]
    # choice-first composites with a pushdown part later are rejected
    import pytest as _pytest
    with _pytest.raises(ValueError, match="pushdown"):
        compose_tables([c1, tables])


def test_parse_request_guided_choice():
    from dynamo_tpu.llm.openai import OpenAIError, parse_request

    base = {"model": "m", "messages": [{"role": "user", "content": "x"}]}
    req = parse_request({**base, "guided_choice": ["yes", "no"]}, chat=True)
    assert req.sampling.guided_choice == ["yes", "no"]

    import pytest as _pytest
    with _pytest.raises(OpenAIError):
        parse_request({**base, "guided_choice": []}, chat=True)
    with _pytest.raises(OpenAIError):
        parse_request({**base, "guided_choice": ["ok", 3]}, chat=True)
    with _pytest.raises(OpenAIError):
        parse_request({**base, "guided_choice": ["a"],
                       "response_format": {"type": "json_object"}}, chat=True)


def test_regex_grammar_basics(tables):
    from dynamo_tpu.engine.grammar import RegexError, compile_regex_vocab

    toks = make_vocab()
    rt = compile_regex_vocab(toks, r"(yes|no)[0-9]+", eos_ids=[EOS])
    rng = np.random.default_rng(9)
    for _ in range(25):
        s, d, st = 1, 0, 0
        out = []
        for _ in range(30):
            m = rt.valid_mask(s, d, st)
            t = int(rng.choice(np.flatnonzero(m)))
            if t == EOS:
                break
            out.append(t)
            s, d, st = rt.advance(s, d, st, t)
        text = decode_ids(toks, out).decode()
        import re
        if out and t == EOS:
            assert re.fullmatch(r"(yes|no)[0-9]+", text), text
    # escapes, classes, quantifiers
    rt = compile_regex_vocab(toks, r"v\d+\.\d+", eos_ids=[EOS])
    s, d, st = 1, 0, 0
    for ch in "v12.3":
        assert rt.valid_mask(s, d, st)[tok_id(toks, ch.encode())], ch
        s, d, st = rt.advance(s, d, st, tok_id(toks, ch.encode()))
    assert rt.valid_mask(s, d, st)[EOS]
    # multi-byte vocab tokens ride the DFA: "123" is one token
    rt = compile_regex_vocab(toks, r"[0-9]+", eos_ids=[EOS])
    assert rt.valid_mask(1, 0, 0)[tok_id(toks, b"123")]
    # unsupported syntax is loud
    import pytest as _pytest
    with _pytest.raises(RegexError):
        compile_regex_vocab(toks, r"a{2,5}", eos_ids=[EOS])
    with _pytest.raises(RegexError):
        compile_regex_vocab(toks, r"(unclosed", eos_ids=[EOS])


def test_parse_request_guided_regex():
    from dynamo_tpu.llm.openai import OpenAIError, parse_request

    base = {"model": "m", "messages": [{"role": "user", "content": "x"}]}
    req = parse_request({**base, "guided_regex": "[a-z]+"}, chat=True)
    assert req.sampling.guided_regex == "[a-z]+"

    import pytest as _pytest
    with _pytest.raises(OpenAIError, match="guided_regex"):
        parse_request({**base, "guided_regex": "(bad"}, chat=True)
    with _pytest.raises(OpenAIError):
        parse_request({**base, "guided_regex": "[a-z]+",
                       "guided_choice": ["a"]}, chat=True)


def test_regex_edge_cases(tables):
    import re

    from dynamo_tpu.engine.grammar import RegexError, compile_regex_vocab

    toks = make_vocab()
    # truncated patterns raise RegexError (not IndexError -> 500s)
    import pytest as _pytest
    for bad in ("a|", "(", "a(", "[a-\\]", "[z-a]", "a\\"):
        with _pytest.raises(RegexError):
            compile_regex_vocab(toks, bad, eos_ids=[EOS])
    # escaped-]-as-range-bound parses; escaped space matches ' '
    rt = compile_regex_vocab(toks, r"[a-z\]]+", eos_ids=[EOS])
    s, d, st = 1, 0, 0
    for ch in b"ab]z":
        assert rt.valid_mask(s, d, st)[1 + ch]
        s, d, st = rt.advance(s, d, st, 1 + ch)
    rt = compile_regex_vocab(toks, r"a\ b", eos_ids=[EOS])
    s, d, st = 1, 0, 0
    for ch in b"a b":
        assert rt.valid_mask(s, d, st)[1 + ch], ch
        s, d, st = rt.advance(s, d, st, 1 + ch)
    assert rt.valid_mask(s, d, st)[EOS]
    # '.' is character-level: never a lone continuation byte, but a full
    # multi-byte char (as byte tokens) fullmatches
    rt = compile_regex_vocab(toks, r".", eos_ids=[EOS])
    assert not rt.valid_mask(1, 0, 0)[1 + 0x80]  # lone continuation
    s, d, st = 1, 0, 0
    for ch in "é".encode("utf-8"):  # 0xC3 0xA9
        assert rt.valid_mask(s, d, st)[1 + ch], hex(ch)
        s, d, st = rt.advance(s, d, st, 1 + ch)
    assert rt.valid_mask(s, d, st)[EOS]
    # negated class likewise: multi-byte chars allowed, excluded ASCII not
    rt = compile_regex_vocab(toks, r"[^a]", eos_ids=[EOS])
    m = rt.valid_mask(1, 0, 0)
    assert not m[1 + ord("a")] and m[1 + ord("b")]
    assert m[1 + 0xC3] and not m[1 + 0x80]


def test_regex_anchors_and_perf(tables):
    import time

    from dynamo_tpu.engine.grammar import RegexError, compile_regex_vocab

    toks = make_vocab()
    # ^...$ anchors are no-ops (fullmatch semantics already)
    rt = compile_regex_vocab(toks, r"^(yes|no)$", eos_ids=[EOS])
    s, d, st = 1, 0, 0
    assert not rt.valid_mask(s, d, st)[tok_id(toks, b"^")]
    for ch in b"yes":
        s, d, st = rt.advance(s, d, st, 1 + ch - 0)  # byte tokens at 1+b
    # mid-pattern anchors are loud
    import pytest as _pytest
    with _pytest.raises(RegexError):
        compile_regex_vocab(toks, r"a^b", eos_ids=[EOS])
    with _pytest.raises(RegexError):
        compile_regex_vocab(toks, r"a$b", eos_ids=[EOS])
    # the exponential-ish pattern compiles (or caps) in bounded CPU time
    # (process_time: wall clock is meaningless under concurrent test load)
    t0 = time.process_time()
    try:
        compile_regex_vocab(toks, "(a|b)*a" + "(a|b)" * 9, eos_ids=[EOS])
    except RegexError:
        pass
    assert time.process_time() - t0 < 5.0


def test_json_schema_translation_and_enforcement():
    """A translatable json_schema becomes a guided_regex (shape enforced);
    untranslatable schemas fall back to generic JSON mode."""
    from dynamo_tpu.engine.grammar import json_schema_to_regex
    from dynamo_tpu.llm.openai import parse_request

    schema = {"type": "object",
              "properties": {"verdict": {"enum": ["pass", "fail"]},
                             "score": {"type": "number"}},
              "required": ["verdict", "score"]}
    base = {"model": "m", "messages": [{"role": "user", "content": "x"}]}
    req = parse_request(
        {**base, "response_format": {
            "type": "json_schema",
            "json_schema": {"name": "r", "schema": schema}}}, chat=True)
    assert req.schema_regex == json_schema_to_regex(schema)
    assert req.sampling.guided_regex == req.schema_regex
    # json_mode stays as the engine-side fallback; the engine's grammar
    # key prefers the regex
    assert req.sampling.json_mode

    # untranslatable (free-form object) -> generic JSON grammar
    req = parse_request(
        {**base, "response_format": {
            "type": "json_schema",
            "json_schema": {"name": "r", "schema": {"type": "object"}}}},
        chat=True)
    assert req.schema_regex is None
    assert req.sampling.json_mode and req.sampling.guided_regex is None


def test_json_schema_regex_rejects_wrong_shape(tables):
    from dynamo_tpu.engine.grammar import (
        compile_regex_vocab, json_schema_to_regex,
    )

    toks = make_vocab()
    schema = {"type": "object",
              "properties": {"ok": {"type": "boolean"},
                             "n": {"type": "integer"}},
              "required": ["ok", "n"]}
    rt = compile_regex_vocab(toks, json_schema_to_regex(schema),
                             eos_ids=[EOS])

    def accepts(text):
        s, d, st = 1, 0, 0
        for b in text.encode():
            if not rt.valid_mask(s, d, st)[1 + b]:
                return False
            s, d, st = rt.advance(s, d, st, 1 + b)
        return bool(rt.valid_mask(s, d, st)[EOS])

    assert accepts('{"ok": true, "n": -3}')
    assert accepts('{"ok":false,"n":0}')
    assert not accepts('{"ok": true}')             # missing property
    assert not accepts('{"n": 1, "ok": true}')     # wrong order (canonical)
    assert not accepts('{"ok": "yes", "n": 1}')    # wrong type


def test_schema_string_fragment_is_strict_json(tables):
    """The schema string regex must reject raw control bytes and illegal
    escapes — exactly like the JSON pushdown grammar's string lexing."""
    from dynamo_tpu.engine.grammar import _RX_STRING, compile_regex_vocab

    toks = make_vocab()
    rt = compile_regex_vocab(toks, _RX_STRING, eos_ids=[EOS])

    def accepts(raw: bytes) -> bool:
        s, d, st = 1, 0, 0
        for b in raw:
            if not rt.valid_mask(s, d, st)[1 + b]:
                return False
            s, d, st = rt.advance(s, d, st, 1 + b)
        return bool(rt.valid_mask(s, d, st)[EOS])

    assert accepts(b'"hello"')
    assert accepts(b'"h\\n i \\u00ff"')
    assert accepts(b'"q\\""')
    assert not accepts(b'"h\ni"')      # raw newline
    assert not accepts(b'"h\x01i"')    # raw control byte
    assert not accepts(b'"h\\qi"')     # illegal escape
    assert not accepts(b'"h\\u12"')    # truncated \\u (can't close)


# ------------------------------------------------ widened schema subset ----
def test_int_range_regex_matches_bruteforce():
    """The digit-range construction is checked exhaustively against
    Python's re over every (lo, hi) window in a probe set, including
    negatives, zero crossings, and half-open ranges."""
    import re

    from dynamo_tpu.engine.grammar import _int_range_rx

    probes = list(range(-140, 141)) + [999, 1000, 1001, 99999, -99999]
    windows = [(-3, 7), (0, 0), (5, 5), (-120, -7), (10, 123), (-1, 1),
               (7, 100), (0, 99), (1, 100000), (-100000, -1)]
    for lo, hi in windows:
        rx = re.compile(_int_range_rx(lo, hi))
        for v in probes:
            want = lo <= v <= hi
            assert bool(rx.fullmatch(str(v))) == want, (lo, hi, v)
    # half-open
    rx = re.compile(_int_range_rx(12, None))
    for v in probes:
        assert bool(rx.fullmatch(str(v))) == (v >= 12), v
    rx = re.compile(_int_range_rx(None, -4))
    for v in probes:
        assert bool(rx.fullmatch(str(v))) == (v <= -4), v
    assert _int_range_rx(5, 4) is None  # empty range


def test_schema_integer_bounds_and_number_fallback():
    import re

    from dynamo_tpu.engine.grammar import json_schema_to_regex

    rx = json_schema_to_regex({"type": "integer", "minimum": 1,
                               "maximum": 10})
    assert rx is not None
    p = re.compile(rx)
    assert p.fullmatch("7") and p.fullmatch("10")
    assert not p.fullmatch("0") and not p.fullmatch("11")
    # draft-2020 exclusive bounds
    rx = json_schema_to_regex({"type": "integer", "exclusiveMinimum": 0,
                               "exclusiveMaximum": 3})
    p = re.compile(rx)
    assert p.fullmatch("1") and p.fullmatch("2")
    assert not p.fullmatch("0") and not p.fullmatch("3")
    # real-valued bounds cannot be regex-enforced -> generic fallback
    assert json_schema_to_regex({"type": "number", "minimum": 0.5}) is None


def test_schema_optional_properties(tables):
    """Optional properties: declared order, required always present,
    optionals independently omittable, commas only between present
    members — enforced at decode time."""
    from dynamo_tpu.engine.grammar import (
        compile_regex_vocab, json_schema_to_regex,
    )

    schema = {"type": "object",
              "properties": {"a": {"type": "integer"},
                             "b": {"type": "boolean"},
                             "c": {"enum": ["x", "y"]}},
              "required": ["b"]}
    rx = json_schema_to_regex(schema)
    assert rx is not None
    toks = make_vocab()
    rt = compile_regex_vocab(toks, rx, eos_ids=[EOS])

    def accepts(text):
        s, d, st = 1, 0, 0
        for b in text.encode():
            if not rt.valid_mask(s, d, st)[1 + b]:
                return False
            s, d, st = rt.advance(s, d, st, 1 + b)
        return bool(rt.valid_mask(s, d, st)[EOS])

    assert accepts('{"a": 1, "b": true, "c": "x"}')
    assert accepts('{"b": false}')
    assert accepts('{"a": -2, "b": true}')
    assert accepts('{"b": true, "c": "y"}')
    assert not accepts('{"a": 1, "c": "x"}')         # missing required b
    assert not accepts('{"b": true,}')               # dangling comma
    assert not accepts('{"c": "x", "b": true}')      # order violated
    assert not accepts('{}')                         # required missing

    # fully-optional object admits {}
    rx = json_schema_to_regex({"type": "object",
                               "properties": {"a": {"type": "integer"}},
                               "required": []})
    rt = compile_regex_vocab(toks, rx, eos_ids=[EOS])
    s, d, st = 1, 0, 0
    for b in b"{}":
        s, d, st = rt.advance(s, d, st, 1 + b)
    assert rt.valid_mask(s, d, st)[EOS]

    # too many optionals -> generic fallback (alternation would explode)
    many = {"type": "object",
            "properties": {f"k{i}": {"type": "boolean"} for i in range(7)},
            "required": []}
    assert json_schema_to_regex(many) is None


def test_schema_anyof_and_type_union(tables):
    import re

    from dynamo_tpu.engine.grammar import json_schema_to_regex

    rx = json_schema_to_regex({"anyOf": [
        {"type": "integer", "minimum": 0},
        {"enum": ["none"]},
    ]})
    p = re.compile(rx)
    assert p.fullmatch("17") and p.fullmatch('"none"')
    assert not p.fullmatch("-1") and not p.fullmatch('"other"')

    # oneOf treated as anyOf (disjoint branches)
    rx = json_schema_to_regex({"oneOf": [{"type": "boolean"},
                                         {"type": "null"}]})
    p = re.compile(rx)
    assert p.fullmatch("true") and p.fullmatch("null")
    assert not p.fullmatch('"true"')

    # nullable via type union
    rx = json_schema_to_regex({"type": ["string", "null"]})
    p = re.compile(rx)
    assert p.fullmatch('"s"') and p.fullmatch("null")
    assert not p.fullmatch("0")

    # a branch that can't translate poisons the whole alternation
    assert json_schema_to_regex({"anyOf": [{"type": "boolean"},
                                           {"type": "object"}]}) is None


def test_schema_untrusted_inputs_never_raise():
    """Schemas are untrusted request bodies: malformed/adversarial bounds
    and conjoined keywords must fall back (None), never raise."""
    from dynamo_tpu.engine.grammar import json_schema_to_regex

    bad = [
        {"type": "integer", "minimum": "5"},          # string bound
        {"type": "integer", "minimum": float("inf")},  # non-finite
        {"type": "integer", "minimum": 1e999},         # inf via literal
        {"type": "integer", "minimum": True},          # bool bound
        {"type": "integer", "minimum": 10 ** 500},     # astronomic
        {"type": "integer", "minimum": 0, "maximum": 10 ** 500},
        {"type": "integer", "minimum": -(10 ** 4400)},
    ]
    for s in bad:
        assert json_schema_to_regex(s) is None, s
    # conjoined siblings that a plain union would drop -> fallback
    assert json_schema_to_regex(
        {"type": "string", "anyOf": [{"type": "string"},
                                     {"type": "integer"}]}) is None
    assert json_schema_to_regex(
        {"type": "integer", "minimum": 5,
         "anyOf": [{"type": "integer"}]}) is None
    assert json_schema_to_regex(
        {"enum": [1, 2], "minimum": 2}) is None
    # enum narrowed by sibling type; fully filtered -> fallback
    import re
    rx = json_schema_to_regex({"type": "string", "enum": ["a", 1, "b"]})
    p = re.compile(rx)
    assert p.fullmatch('"a"') and p.fullmatch('"b"') and not p.fullmatch("1")
    assert json_schema_to_regex({"type": "string", "enum": [1, 2]}) is None


def test_schema_untrusted_structures_never_raise():
    """More adversarial shapes: list-typed enum siblings and malformed
    ``required`` fall back instead of raising."""
    from dynamo_tpu.engine.grammar import json_schema_to_regex

    assert json_schema_to_regex(
        {"type": ["string", "null"], "enum": ["a", None]}) is None
    assert json_schema_to_regex(
        {"type": "object", "properties": {"a": {"type": "integer"}},
         "required": 5}) is None
    assert json_schema_to_regex(
        {"type": "object", "properties": {"a": {"type": "integer"}},
         "required": "a"}) is None
    assert json_schema_to_regex(
        {"type": "object", "properties": {"a": {"type": "integer"}},
         "required": [1]}) is None
