"""Loop drivers, one module per traffic `kind`. Each has
`setup(sess, traffic, reqs)` -> state, `window(sess, state, seconds)`
-> {"open", "close"}, `settle(sess, state, close)` (wait for what the
window owes) and `more(sess, state, steps, prefill)` (keep the loop going for a
traced stretch)."""
