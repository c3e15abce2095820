"""ISSUE 30, the cases of ``test_int4_body.py`` at the K's that are
chunked: 11,008 (Llama-2's ``down_proj``: two chunks of 2 x 1,024 +
704 packed rows, g = 172, a stack that cannot be blocked in place) and
14,336 (Mistral's: two equal chunks of 3 x 1,024 + 512).
A file of their own so that another worker takes them."""

from test_int4_body import (_drop_executables,  # noqa: F401  (fixtures)
                            _leave_no_executables, check_body, every_case)


@every_case([11008, 14336])
def test_body_matches_reference_chunked(mode, m, k, n, form):
    check_body(mode, m, k, n, form)
