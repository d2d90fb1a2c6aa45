"""Spark-compatible URI parsing: protocol / host / query / query(param).

Reference: /root/reference/src/main/cpp/src/parse_uri.cu (uri_parts :45,
validate_uri with UTF-8 and %-escape checks :92-494, find_query_part :495,
two-kernel strings pattern :774-875) and ParseURI.java:36-86. The behavioral
contract is java.net.URI (the reference test's oracle, ParseURITest.java):
RFC 2396 grammar with Java's deviations — non-US-ASCII "other" characters
are legal wherever escapes are, space/control characters are never legal,
server-based authority parsing falls back to registry-based (host becomes
null but the URI stays valid), and an invalid URI nulls every component.

TPU-native design: one jitted kernel over the padded (n, L) char matrix.
Components are located with masked min-reductions (first ':' '/' '?' '#'
etc.), character legality is a 256-entry class-table gather per component,
UTF-8 structure and Unicode space/control rejection run as shifted-compare
vector ops, and substrings are produced with the standard measure->gather
pattern. No per-row loops anywhere; the query-parameter search is a
correlation over pair-start positions rather than a split loop.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.column import Column, _round_bucket, strings_from_padded

# ---------------------------------------------------------------------------
# Character class tables (host-built, RFC 2396 + java.net.URI deviations)
# ---------------------------------------------------------------------------

_ALPHA = set(range(ord("a"), ord("z") + 1)) | set(range(ord("A"), ord("Z") + 1))
_DIGIT = set(range(ord("0"), ord("9") + 1))
_ALNUM = _ALPHA | _DIGIT
_MARK = set(map(ord, "-_.!~*'()"))
_UNRESERVED = _ALNUM | _MARK
_RESERVED = set(map(ord, ";/?:@&=+$,[]"))  # java adds [] for IPv6


def _table(allowed, pct=True, other=True):
    """256-entry legality table. `pct` admits '%' (escape lead byte; the
    following two hex digits are validated separately); `other` admits
    non-ASCII bytes (validated separately as UTF-8 / control / space)."""
    t = np.zeros(256, np.bool_)
    for c in allowed:
        t[c] = True
    if pct:
        t[ord("%")] = True
    if other:
        t[128:] = True
    return t


_T_SCHEME = _table(_ALNUM | set(map(ord, "+-.")), pct=False, other=False)
_T_USERINFO = _table(_UNRESERVED | set(map(ord, ";:&=+$,")))
_T_REGISTRY = _table(_UNRESERVED | set(map(ord, "$,;:@&=+")))
_T_PATH = _table(_UNRESERVED | set(map(ord, ":@&=+$,;/")))
_T_URIC = _table(_UNRESERVED | _RESERVED)            # query, fragment, opaque
_T_HOSTNAME = _table(_ALNUM | set(map(ord, "-.")), pct=False, other=False)
_T_IPV6 = _table(set(map(ord, "0123456789abcdefABCDEF:.")), pct=False,
                 other=False)
_T_HEX = _table(set(map(ord, "0123456789abcdefABCDEF")), pct=False, other=False)
_T_DIGITS = _table(_DIGIT, pct=False, other=False)
_T_ALNUM = _table(_ALNUM, pct=False, other=False)
_T_ALPHA = _table(_ALPHA, pct=False, other=False)

_BIG = np.int32(1 << 30)  # "not found" sentinel position


def _first_at_or_after(mask, start, L):
    """Per-row position of the first True in `mask` at or after `start`
    (column vector), else _BIG. mask: (n, L) bool; start: (n, 1) int32."""
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    cand = jnp.where(mask & (pos >= start), pos, _BIG)
    return jnp.min(cand, axis=1).astype(jnp.int32)


def _all_in_range(ok, start, end, L):
    """True when every position in [start, end) satisfies `ok` (n, L)."""
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    in_r = (pos >= start) & (pos < end)
    return jnp.all(ok | ~in_r, axis=1)


def _count_in_range(mask, start, end, L):
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    in_r = (pos >= start) & (pos < end)
    return jnp.sum(mask & in_r, axis=1).astype(jnp.int32)


def _class_ok(chars, table):
    return jnp.asarray(table)[chars.astype(jnp.int32)]


def _ipv4_ok(chars, start, end, L, is_ch, pos):
    """Exact dotted-quad IPv4 over [start, end): 4 quads of 1-3 digits,
    each <= 255 (java Parser.parseIPv4Address / scanByte)."""
    in_r = (pos >= start[:, None]) & (pos < end[:, None])
    digit = _class_ok(chars, _T_DIGITS) & in_r
    dot = is_ch(".") & in_r
    chars_ok = jnp.all(digit | dot | ~in_r, axis=1)
    three_dots = jnp.sum(dot, axis=1) == 3
    prev_dot = jnp.concatenate([jnp.zeros_like(dot[:, :1]), dot[:, :-1]],
                               axis=1)
    adj = jnp.any(dot & prev_dot, axis=1)
    at_start = pos == start[:, None]
    at_last = pos == end[:, None] - 1
    edge_dot = jnp.any(dot & (at_start | at_last), axis=1)
    qstart = digit & (at_start | prev_dot)
    stop = jnp.where(dot | (pos >= end[:, None]), pos, _BIG)
    run_end = jax.lax.associative_scan(jnp.minimum, stop, reverse=True, axis=1)
    qlen = jnp.where(qstart, run_end - pos, 1)
    len_ok = jnp.all(qlen <= 3, axis=1)
    ch1 = jnp.concatenate([chars[:, 1:], jnp.zeros_like(chars[:, :1])], axis=1)
    ch2 = jnp.concatenate([chars[:, 2:], jnp.zeros_like(chars[:, :2])], axis=1)
    over255 = (chars > ord("2")) | \
        ((chars == ord("2")) & ((ch1 > ord("5")) |
                                ((ch1 == ord("5")) & (ch2 > ord("5")))))
    big_quad = jnp.any(qstart & (qlen == 3) & over255, axis=1)
    return chars_ok & three_dots & ~adj & ~edge_dot & len_ok & ~big_quad & \
        (end > start)


# ---------------------------------------------------------------------------
# Global validation: UTF-8 structure, control chars, Unicode spaces, escapes
# ---------------------------------------------------------------------------


def _utf8_and_charset_valid(chars, lens, L):
    """Per-row: bytes form valid UTF-8; no ISO-control or Unicode-space
    code points (java.net.URI: 'The space character and control characters
    are never legal'). Returns (n,) bool."""
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    live = pos < lens[:, None]
    c = chars.astype(jnp.int32)
    nxt1 = jnp.concatenate([c[:, 1:], jnp.zeros_like(c[:, :1])], axis=1)
    nxt2 = jnp.concatenate([c[:, 2:], jnp.zeros_like(c[:, :2])], axis=1)
    live1 = jnp.concatenate([live[:, 1:], jnp.zeros_like(live[:, :1])], axis=1)
    live2 = jnp.concatenate([live[:, 2:], jnp.zeros_like(live[:, :2])], axis=1)
    live3 = jnp.concatenate([live[:, 3:], jnp.zeros_like(live[:, :3])], axis=1)

    is_cont = (c & 0xC0) == 0x80
    cont1 = (nxt1 & 0xC0) == 0x80
    cont2 = (nxt2 & 0xC0) == 0x80
    nxt3 = jnp.concatenate([c[:, 3:], jnp.zeros_like(c[:, :3])], axis=1)
    cont3 = (nxt3 & 0xC0) == 0x80

    lead1 = c < 0x80
    lead2 = (c >= 0xC2) & (c <= 0xDF)
    lead3 = (c >= 0xE0) & (c <= 0xEF)
    lead4 = (c >= 0xF0) & (c <= 0xF4)
    bad_lead = ((c == 0xC0) | (c == 0xC1) | (c >= 0xF5)) & live

    ok2 = lead2 & cont1 & live1
    # overlong/surrogate exclusions for 3-byte leads
    e0_ok = (c != 0xE0) | (nxt1 >= 0xA0)
    ed_ok = (c != 0xED) | (nxt1 <= 0x9F)
    ok3 = lead3 & cont1 & cont2 & live2 & e0_ok & ed_ok
    f0_ok = (c != 0xF0) | (nxt1 >= 0x90)
    f4_ok = (c != 0xF4) | (nxt1 <= 0x8F)
    ok4 = lead4 & cont1 & cont2 & cont3 & live3 & f0_ok & f4_ok

    # every continuation byte must be claimed by the preceding lead
    prev1 = jnp.concatenate([jnp.zeros_like(c[:, :1]), c[:, :-1]], axis=1)
    prev2 = jnp.concatenate([jnp.zeros_like(c[:, :2]), c[:, :-2]], axis=1)
    prev3 = jnp.concatenate([jnp.zeros_like(c[:, :3]), c[:, :-3]], axis=1)
    claimed = (((prev1 >= 0xC2) & (prev1 <= 0xF4)) |
               ((prev2 >= 0xE0) & (prev2 <= 0xF4)) |
               ((prev3 >= 0xF0) & (prev3 <= 0xF4)))
    seq_ok = jnp.where(live,
                       jnp.where(lead1, True,
                                 jnp.where(is_cont, claimed,
                                           ok2 | ok3 | ok4)) & ~bad_lead,
                       True)

    # ASCII control + space
    ascii_bad = ((c < 0x21) | (c == 0x7F)) & live
    # U+0080-U+009F (C2 80-9F) and U+00A0 (C2 A0)
    c2_bad = (c == 0xC2) & (nxt1 >= 0x80) & (nxt1 <= 0xA0) & live
    # U+1680 (E1 9A 80)
    u1680 = (c == 0xE1) & (nxt1 == 0x9A) & (nxt2 == 0x80) & live
    # U+2000-U+200A, U+2028, U+2029, U+202F (E2 80 xx)
    e280 = (c == 0xE2) & (nxt1 == 0x80) & live
    u2000 = e280 & (((nxt2 >= 0x80) & (nxt2 <= 0x8A)) | (nxt2 == 0xA8) |
                    (nxt2 == 0xA9) | (nxt2 == 0xAF))
    # U+205F (E2 81 9F)
    u205f = (c == 0xE2) & (nxt1 == 0x81) & (nxt2 == 0x9F) & live
    # U+3000 (E3 80 80)
    u3000 = (c == 0xE3) & (nxt1 == 0x80) & (nxt2 == 0x80) & live
    space_bad = c2_bad | u1680 | u2000 | u205f | u3000

    return jnp.all(seq_ok & ~ascii_bad & ~space_bad, axis=1)


def _escapes_valid(chars, lens, L):
    """Every '%' is followed by two hex digits (within the row)."""
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    live = pos < lens[:, None]
    is_pct = (chars == ord("%")) & live
    hexok = _class_ok(chars, _T_HEX)
    h1 = jnp.concatenate([hexok[:, 1:], jnp.zeros_like(hexok[:, :1])], axis=1)
    h2 = jnp.concatenate([hexok[:, 2:], jnp.zeros_like(hexok[:, :2])], axis=1)
    l2 = pos + 2 < lens[:, None]
    return jnp.all(~is_pct | (h1 & h2 & l2), axis=1)


# ---------------------------------------------------------------------------
# The parser kernel
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("L",))
def _parse_kernel(chars, lens, *, L):
    """Locate and validate URI components.

    Returns dict of vectors: row_valid, and (start, end, present) for
    scheme, host, query. Follows java.net.URI's Parser: scheme iff a ':'
    precedes any '/?#'; opaque vs hierarchical; '//' authority with
    server->registry fallback; strict hostname/IPv6 grammar for getHost().
    """
    n = chars.shape[0]
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    lens2 = lens[:, None]
    live = pos < lens2
    zero = jnp.zeros((n,), jnp.int32)

    def ch_at(idx):
        """chars[row, idx] with OOB -> 0."""
        safe = jnp.clip(idx, 0, L - 1)
        v = jnp.take_along_axis(chars, safe[:, None], axis=1)[:, 0]
        return jnp.where((idx >= 0) & (idx < lens), v, jnp.uint8(0))

    is_ch = lambda b: (chars == ord(b)) & live
    first = lambda b, start: _first_at_or_after(is_ch(b), start[:, None], L)

    invalid = ~_utf8_and_charset_valid(chars, lens, L)
    invalid |= ~_escapes_valid(chars, lens, L)

    # ---- scheme -----------------------------------------------------------
    colon0 = first(":", zero)
    slash0 = first("/", zero)
    q0 = first("?", zero)
    h0 = first("#", zero)
    delim0 = jnp.minimum(jnp.minimum(slash0, q0), jnp.minimum(h0, lens))
    has_scheme = colon0 < delim0
    scheme_ok = (colon0 > 0) & _class_ok(ch_at(zero), _T_ALPHA) & \
        _all_in_range(_class_ok(chars, _T_SCHEME), 1, colon0[:, None], L)
    invalid |= has_scheme & ~scheme_ok
    # a ':' at position 0 (before any /?#) is "expected scheme name"
    invalid |= (colon0 == 0) & (colon0 < delim0)

    ssp_start = jnp.where(has_scheme, colon0 + 1, 0)
    # fragment delimiter anywhere after ssp_start
    frag = first("#", ssp_start)
    body_end = jnp.minimum(frag, lens)        # ssp body (before fragment)
    # "Expected scheme-specific part": empty ssp after "scheme:"
    invalid |= has_scheme & (ssp_start >= body_end)

    # ---- opaque vs hierarchical ------------------------------------------
    c_ssp = ch_at(ssp_start)
    hier = ~has_scheme | (c_ssp == ord("/")) | (ssp_start >= body_end)
    opaque = ~hier
    # opaque: first char uric-not-slash (guaranteed: not '/'), rest uric
    uric_ok = _class_ok(chars, _T_URIC)
    invalid |= opaque & ~_all_in_range(uric_ok, ssp_start[:, None],
                                       body_end[:, None], L)

    # ---- hierarchical: authority / path / query --------------------------
    two_slash = (c_ssp == ord("/")) & (ch_at(ssp_start + 1) == ord("/"))
    has_auth = hier & two_slash
    auth_start = ssp_start + 2
    stop_mask = is_ch("/") | is_ch("?") | is_ch("#")
    auth_end = jnp.minimum(
        _first_at_or_after(stop_mask, auth_start[:, None], L), lens)
    auth_end = jnp.where(has_auth, auth_end, ssp_start)
    empty_auth = has_auth & (auth_end == auth_start)
    # java deviation: empty authority legal only before a non-empty path or
    # query (within the ssp; a lone fragment does not count)
    invalid |= empty_auth & (auth_start >= body_end)

    path_start = jnp.where(has_auth, auth_end, ssp_start)
    qmark = _first_at_or_after(is_ch("?") & (pos >= path_start[:, None]),
                               path_start[:, None], L)
    path_end = jnp.minimum(jnp.minimum(qmark, frag), lens)
    path_ok = _all_in_range(_class_ok(chars, _T_PATH),
                            path_start[:, None], path_end[:, None], L)
    invalid |= hier & ~path_ok

    has_query = hier & (qmark < jnp.minimum(frag, lens))
    query_start = qmark + 1
    query_end = jnp.minimum(frag, lens)
    invalid |= has_query & ~_all_in_range(uric_ok, query_start[:, None],
                                          query_end[:, None], L)

    has_frag = frag < lens
    invalid |= has_frag & ~_all_in_range(uric_ok, frag[:, None] + 1,
                                         lens2, L)

    # ---- authority: server-based parse with registry fallback ------------
    amp = _first_at_or_after(is_ch("@") & (pos < auth_end[:, None]),
                             auth_start[:, None], L)
    has_user = has_auth & (amp < auth_end)
    user_ok = _all_in_range(_class_ok(chars, _T_USERINFO),
                            auth_start[:, None], amp[:, None], L)
    host_start = jnp.where(has_user, amp + 1, auth_start)

    # port: the last ':' in [host_start, auth_end) splits host:port
    colon_mask = is_ch(":") & (pos >= host_start[:, None]) & \
        (pos < auth_end[:, None])
    last_colon = jnp.max(jnp.where(colon_mask, pos, -1), axis=1).astype(jnp.int32)

    is_v6 = has_auth & (ch_at(host_start) == ord("["))
    # ---- IPv6 literal (java Parser.parseIPv6Reference semantics) ---------
    rb = _first_at_or_after(is_ch("]") & (pos < auth_end[:, None]),
                            host_start[:, None], L)
    v6_close_ok = rb < auth_end
    a6 = host_start + 1                       # inner region [a6, rb)
    v6_chars_ok = _all_in_range(_class_ok(chars, _T_IPV6),
                                a6[:, None], rb[:, None], L)
    in6 = (pos >= a6[:, None]) & (pos < rb[:, None])
    colon6 = is_ch(":") & in6
    nxt_colon6 = jnp.concatenate([colon6[:, 1:],
                                  jnp.zeros_like(colon6[:, :1])], axis=1)
    dc_pair = colon6 & nxt_colon6             # '::' occurrences
    n_dc = jnp.sum(dc_pair, axis=1).astype(jnp.int32)
    has_dc = n_dc > 0
    # lone ':' at either edge is illegal (':x' / 'x:'), '::' there is fine
    lead_colon = (ch_at(a6) == ord(":")) & (ch_at(a6 + 1) != ord(":"))
    tail_colon = (ch_at(rb - 1) == ord(":")) & (ch_at(rb - 2) != ord(":"))
    # groups: runs of non-':' chars; group start = non-':' preceded by
    # ':' or the region edge
    non_colon6 = in6 & ~colon6
    prev_nc = jnp.concatenate([jnp.zeros_like(non_colon6[:, :1]),
                               non_colon6[:, :-1]], axis=1)
    gstart = non_colon6 & (~prev_nc | (pos == a6[:, None]))
    # per-position group end: next ':' or rb (suffix-min scan)
    nxt_stop = jnp.where(colon6 | (pos >= rb[:, None]), pos, _BIG)
    # suffix min of nxt_stop per row gives, at p, the first stop >= p
    run_end = jax.lax.associative_scan(jnp.minimum, nxt_stop, reverse=True,
                                       axis=1)
    glen = jnp.where(gstart, run_end - pos, 0)
    has_dot6 = jnp.zeros_like(gstart)
    dot_in_group = is_ch(".") & in6
    # a group contains '.' iff any '.' in [p, run_end) — propagate via scan
    dot_pos = jnp.where(dot_in_group, pos, _BIG)
    first_dot_from = jax.lax.associative_scan(jnp.minimum, dot_pos,
                                              reverse=True, axis=1)
    g_has_dot = gstart & (first_dot_from < run_end)
    # embedded IPv4 group must be the last group (run_end == rb)
    v4_last_ok = jnp.all(~g_has_dot | (run_end == rb[:, None]), axis=1)
    n_v4 = jnp.sum(g_has_dot, axis=1).astype(jnp.int32)
    hexg = gstart & ~g_has_dot
    hex_len_ok = jnp.all(~hexg | ((glen >= 1) & (glen <= 4)), axis=1)
    # '.' groups may not contain ':' by construction; validate quad shape
    # with the shared IPv4 checker over [group start, rb)
    v4_ok6 = _ipv4_ok(chars, jnp.where(jnp.any(g_has_dot, axis=1),
                                       jnp.max(jnp.where(g_has_dot, pos, -1),
                                               axis=1).astype(jnp.int32),
                                       zero),
                      rb, L, is_ch, pos)
    n_hexg = jnp.sum(hexg, axis=1).astype(jnp.int32)
    v6_bytes = 2 * n_hexg + 4 * n_v4
    count_ok = jnp.where(has_dc, v6_bytes <= 14, v6_bytes == 16)
    v6_inner_ok = v6_chars_ok & (n_dc <= 1) & ~lead_colon & ~tail_colon & \
        hex_len_ok & v4_last_ok & (n_v4 <= 1) & count_ok & \
        (~jnp.any(g_has_dot, axis=1) | v4_ok6)
    v6_port_sep = rb + 1
    v6_has_port = v6_close_ok & (v6_port_sep < auth_end)
    v6_port_ok = (~v6_has_port) | ((ch_at(v6_port_sep) == ord(":")) &
                                   _all_in_range(_class_ok(chars, _T_DIGITS),
                                                 v6_port_sep[:, None] + 1,
                                                 auth_end[:, None], L))
    v6_ok = v6_close_ok & v6_inner_ok & v6_port_ok
    v6_host_end = rb + 1                      # getHost() keeps the brackets

    has_port = (~is_v6) & (last_colon >= host_start)
    host_end = jnp.where(has_port, last_colon, auth_end)
    port_ok = (~has_port) | _all_in_range(_class_ok(chars, _T_DIGITS),
                                          last_colon[:, None] + 1,
                                          auth_end[:, None], L)

    # ---- hostname / IPv4 (java parseHostname: labels of alphanum/'-',
    # no '-' at label edges, optional trailing '.', and the LAST label must
    # start with a letter; otherwise the host must parse as an exact IPv4)
    hn_chars_ok = _all_in_range(_class_ok(chars, _T_HOSTNAME),
                                host_start[:, None], host_end[:, None], L)
    in_host = (pos >= host_start[:, None]) & (pos < host_end[:, None])
    is_dot = is_ch(".") & in_host
    is_dash = is_ch("-") & in_host
    nxt_dot = jnp.concatenate([is_dot[:, 1:], jnp.zeros_like(is_dot[:, :1])],
                              axis=1)
    prv_dot = jnp.concatenate([jnp.zeros_like(is_dot[:, :1]), is_dot[:, :-1]],
                              axis=1)
    at_start = pos == host_start[:, None]
    at_last = pos == host_end[:, None] - 1
    # '-' adjacent to '.', at host edges -> bad; '.' adjacent to '.' -> bad
    dash_bad = is_dash & (nxt_dot | prv_dot | at_start | at_last)
    dot_bad = is_dot & (prv_dot | at_start)
    label_ok = hn_chars_ok & (host_end > host_start) & \
        ~jnp.any(dash_bad | dot_bad, axis=1)
    # last label start: after the last '.' (ignoring one trailing '.')
    trailing_dot = ch_at(host_end - 1) == ord(".")
    eff_end = host_end - trailing_dot.astype(jnp.int32)
    lastdot = jnp.max(jnp.where(is_dot & (pos < eff_end[:, None]), pos, -1),
                      axis=1).astype(jnp.int32)
    last_label = jnp.maximum(lastdot + 1, host_start)
    last_alpha = _class_ok(ch_at(last_label), _T_ALPHA)
    hostname_ok = label_ok & last_alpha
    ipv4_host_ok = _ipv4_ok(chars, host_start, host_end, L, is_ch, pos)
    host_ok = hostname_ok | ipv4_host_ok

    server_ok = has_auth & (~has_user | user_ok) & \
        jnp.where(is_v6, v6_ok, host_ok & port_ok)
    # registry fallback: every authority char legal for reg_name/other
    registry_ok = _all_in_range(_class_ok(chars, _T_REGISTRY) |
                                (is_ch("@")),
                                auth_start[:, None], auth_end[:, None], L)
    invalid |= has_auth & ~empty_auth & ~server_ok & ~registry_ok

    host_present = has_auth & ~empty_auth & server_ok & ~invalid
    out_host_start = host_start
    out_host_end = jnp.where(is_v6, v6_host_end, host_end)

    row_valid = ~invalid
    return dict(
        row_valid=row_valid,
        scheme_present=has_scheme & row_valid,
        scheme_start=zero, scheme_end=colon0,
        host_present=host_present,
        host_start=out_host_start, host_end=out_host_end,
        query_present=has_query & row_valid,
        query_start=query_start, query_end=query_end,
    )


# ---------------------------------------------------------------------------
# Substring assembly
# ---------------------------------------------------------------------------


def _extract(chars_padded, present, start, end, validity, out_pad_to=None):
    """Build a string column from per-row [start, end) spans of the padded
    input (gather half of the measure->gather pattern). `out_pad_to` is the
    static output-width bound that lets the whole parse trace under jax.jit;
    left None it is measured from the data (host sync)."""
    out_len = jnp.where(present, end - start, 0).astype(jnp.int32)
    if out_pad_to is None:
        max_len = int(jnp.max(out_len)) if out_len.shape[0] else 0
        Lout = _round_bucket(max(1, max_len))
    else:
        Lout = out_pad_to
        if out_len.shape[0] and not isinstance(out_len, jax.core.Tracer):
            # a too-small bound silently truncates the gathered chars while
            # offsets still claim the full span (same guard as padded_chars)
            m = int(jnp.max(out_len))
            if m > Lout:
                raise ValueError(
                    f"out_pad_to={Lout} is smaller than the longest extracted "
                    f"span ({m})")
    idx = start[:, None] + jnp.arange(Lout, dtype=jnp.int32)[None, :]
    take = jnp.take_along_axis(chars_padded,
                               jnp.clip(idx, 0, chars_padded.shape[1] - 1),
                               axis=1)
    in_r = jnp.arange(Lout, dtype=jnp.int32)[None, :] < out_len[:, None]
    out_valid = present
    if validity is not None:
        out_valid = out_valid & validity
        out_len = jnp.where(validity, out_len, 0)
    return strings_from_padded(jnp.where(in_r, take, jnp.uint8(0)), out_len,
                               out_valid)


def _parse(column: Column, pad_to=None):
    if not column.dtype.is_string:
        raise TypeError("parse_uri expects a string column")
    padded, lens = column.padded_chars(pad_to)
    parts = _parse_kernel(padded, lens, L=padded.shape[1])
    return padded, lens, parts


def parse_uri_to_protocol(column: Column, pad_to=None,
                          out_pad_to=None) -> Column:
    """getScheme() per row; null for invalid URIs (parse_uri.cu:877).

    `pad_to`/`out_pad_to` are optional static input/output width bounds that
    make the call traceable under an enclosing jax.jit."""
    padded, _, p = _parse(column, pad_to)
    return _extract(padded, p["scheme_present"], p["scheme_start"],
                    p["scheme_end"], column.validity, out_pad_to)


def parse_uri_to_host(column: Column, pad_to=None, out_pad_to=None) -> Column:
    """getHost() per row: server-based authorities only (parse_uri.cu:905)."""
    padded, _, p = _parse(column, pad_to)
    return _extract(padded, p["host_present"], p["host_start"],
                    p["host_end"], column.validity, out_pad_to)


def parse_uri_to_query(column: Column, pad_to=None, out_pad_to=None) -> Column:
    """getRawQuery() per row (parse_uri.cu:933)."""
    padded, _, p = _parse(column, pad_to)
    return _extract(padded, p["query_present"], p["query_start"],
                    p["query_end"], column.validity, out_pad_to)


@partial(jax.jit, static_argnames=("L", "Lp", "require_nonempty_key"))
def _find_param_kernel(chars, param, plens, qstart, qend, qpresent, *,
                       L, Lp, require_nonempty_key):
    """Locate the value of the first query pair whose key equals `param`.

    Pairs split on '&'; a pair matches when [pair_start, pair_start+plen)
    equals the param bytes and the next char is '=' (the reference also
    requires a non-empty key for the literal variant —
    ParseURITest.java:110 idx > 0 vs :149 idx >= 0).
    """
    n = chars.shape[0]
    pos = jnp.arange(L, dtype=jnp.int32)[None, :]
    in_q = (pos >= qstart[:, None]) & (pos < qend[:, None])
    is_amp = (chars == ord("&")) & in_q
    prev_amp = jnp.concatenate([jnp.zeros_like(is_amp[:, :1]),
                                is_amp[:, :-1]], axis=1)
    pair_start = (pos == qstart[:, None]) | (prev_amp & in_q)

    # correlation match of param bytes at every pair start; fori_loop keeps
    # the HLO size independent of the param-width bucket Lp
    ext = jnp.concatenate([chars, jnp.zeros((n, Lp), jnp.uint8)], axis=1)

    def body(i, match):
        shifted = jax.lax.dynamic_slice(ext, (0, i), (n, L))
        p_i = jax.lax.dynamic_slice(param, (0, i), (n, 1))
        live_i = i < plens[:, None]
        return match & (~live_i | (shifted == p_i))

    match = jax.lax.fori_loop(0, Lp, body, jnp.ones((n, L), jnp.bool_))
    eq_pos = pos + plens[:, None]
    eq_char = jnp.take_along_axis(
        chars, jnp.clip(eq_pos, 0, L - 1), axis=1)
    match &= pair_start & in_q & (eq_char == ord("=")) & \
        (eq_pos < qend[:, None])
    if require_nonempty_key:
        match &= plens[:, None] > 0
    first_match = jnp.min(jnp.where(match, pos, _BIG), axis=1).astype(jnp.int32)
    found = qpresent & (first_match < _BIG)
    vstart = first_match + plens + 1
    vend = jnp.minimum(
        _first_at_or_after(is_amp, vstart[:, None], L), qend)
    return found, vstart, vend


def _query_param(column: Column, param_padded, param_lens,
                 require_nonempty_key: bool, pad_to=None,
                 out_pad_to=None) -> Column:
    padded, _, p = _parse(column, pad_to)
    L = padded.shape[1]
    Lp = param_padded.shape[1]
    found, vstart, vend = _find_param_kernel(
        padded, param_padded, param_lens, p["query_start"], p["query_end"],
        p["query_present"], L=L, Lp=Lp,
        require_nonempty_key=require_nonempty_key)
    return _extract(padded, found, vstart, vend, column.validity, out_pad_to)


def parse_uri_to_query_literal(column: Column, param: str, pad_to=None,
                               out_pad_to=None) -> Column:
    """Value of `param` in each row's query (ParseURI.java:70). A match
    needs a non-empty key equal to `param`."""
    n = column.length
    pb = np.frombuffer(param.encode(), np.uint8)
    Lp = _round_bucket(max(1, len(pb)))
    pad = np.zeros((n, Lp), np.uint8)
    pad[:, :len(pb)] = pb[None, :]
    plens = jnp.full((n,), len(pb), jnp.int32)
    return _query_param(column, jnp.asarray(pad), plens, True, pad_to,
                        out_pad_to)


def parse_uri_to_query_column(column: Column, params: Column, pad_to=None,
                              out_pad_to=None, param_pad_to=None) -> Column:
    """Per-row parameter column variant (ParseURI.java: parseURIQueryWithColumn)."""
    if not params.dtype.is_string:
        raise TypeError("params must be a string column")
    ppad, plens = params.padded_chars(param_pad_to)
    out = _query_param(column, ppad, plens, False, pad_to, out_pad_to)
    if params.validity is not None:
        merged = out.null_mask & params.validity
        return out.with_validity(merged)
    return out
