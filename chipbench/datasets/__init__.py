"""The clients' data, one module per kind, named by a configuration's
``"data"`` key.  Each module gives the harness and the reference what
they need of the data, so neither names a data set:

* ``build_trainer(cfg, fl)``: the program's trainer over this data, as
  the configuration states it;
* ``clients(cfg)``: the benchmark's own copy of every client's data;
* ``n_samples(client)``: a client's sample count (its merge weight);
* ``client_stream(cfg, client, seed)``: ``(xs, ys)``, the client's local
  batches of one round, stream for stream as the program draws them;
* ``data_gap(trainer, ours)``: the clients whose data the program's
  trainer holds otherwise than ``ours``;
* ``check(cfg, trainer)``: ``None``, or what in the trainer's clients,
  sample counts and local steps differs from the configuration.
"""
