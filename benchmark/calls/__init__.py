"""Client calls, one module per call named by a mix's ``"call"`` key.

Each module exposes:

- ``send(ds, type_name, cqls)``: the timed call; returns once the client
  holds the answer on the host.
- ``keep(answer)``: what of the answer is kept for the check (untimed).
- ``expected(referee, request)``: the plain reference's answer.
- ``judge(kept, expected, fid_base) -> dict``: the numbers compared, by
  name, each summed over requests (feature ids less ``fid_base`` are row
  ordinals); ``LIMITS`` gives each one's limit.
- ``control(referee32, request)``: the control's answer in the program's
  place (the reference computed in float32).
"""
