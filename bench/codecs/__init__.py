"""Link codecs as the benchmark reckons them, one file per codec family
(``transport.codec`` is ``<family>[:<arg>]``): ``lossy``, the value a
receiver decodes."""
