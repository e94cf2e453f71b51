"""Page pool high-water as a share of the pool (``KVStats``), the
fullest server's, in percent."""


def read(win):
    shares = [st.pages_highwater / st.pool_pages
              for st in win.kv.values() if st.pool_pages]
    return 100.0 * max(shares) if shares else None
